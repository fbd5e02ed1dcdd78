"""Host work of the campaign driver per chunk, in ms: the time of the
window's ``campaign()`` calls outside ``campaign.wait`` and
``campaign.result`` (planning, dispatch, the fold's enqueue, draining,
and what no span names) over the chunks, from the program's spans on
the trace's clock (``program_spans``), over the window's whole
extent."""
from bench import program_spans


def read(ctx):
    split = program_spans.campaign_split(ctx)
    if split is None:
        return None
    return split["host_ms"] / split["chunks"]
