"""Time the campaign driver's host blocks on the device per chunk, in
ms: the window's ``campaign.wait`` (a chunk's summary) and
``campaign.result`` (the final accumulator) spans over the chunks, from
the program's spans on the trace's clock (``program_spans``), over the
window's whole extent."""
from bench import program_spans


def read(ctx):
    split = program_spans.campaign_split(ctx)
    if split is None:
        return None
    return split["wait_ms"] / split["chunks"]
