#!/usr/bin/env python3
"""Readings that set the limit of a cell served by ``runners.serve_dsv2``:
the program's sound runs on many seeds and the control on the same
seeds, in one process (``readings.py`` does the same for the other
cells).

    python3 bench/readings_dsv2.py --workload dsv2lite.saturated \
        --seeds 101,102,... --seconds <s> [--control N] [--fault offset]

For each seed: the cell's own set-up and a window of ``--seconds`` at
the cell's own sizes and load, the check's number (``logit_gap_max``),
and the control's: at every position of the sampled requests' prompts
and served tokens, the token that a float8_e4m3 reference puts first,
read by its gap under the float32 reference.  Where the control runs,
the routing of the same sequences is compared too: the experts the
program's own bfloat16 forward pass (``transformer.forward``, the
prefill's mathematics over the whole sequence) chooses in each MoE layer
against the float32 reference's choice, and how near the reference's
6th and 7th experts' probabilities lie where the two differ.

``--fault offset`` plants a fault in the program to read it against the
limit: the layer is told the wrong held range (the next share's experts),
so it routes the wrong slots into the weights it holds.

One JSON line per seed goes to standard output; the benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def program_routing(cfg: dict, weights, seqs):
    """The experts the program's forward pass routes each token of
    ``seqs`` to: (sequences, MoE layers, positions, k)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.runners.serve_dsv2 import model_config
    from repro.models import moe, transformer

    mcfg = model_config(cfg)
    seen = []
    orig = moe._routed

    def spy(p, m, xf, activation):
        out = orig(p, m, xf, activation)
        jax.debug.callback(lambda t: seen.append(np.asarray(t)), out[3],
                           ordered=True)
        return out

    moe._routed = spy
    try:
        fwd = jax.jit(lambda w, t: transformer.forward(mcfg, w,
                                                       {"tokens": t})[0])
        out = []
        for seq in seqs:
            seen.clear()
            jax.block_until_ready(fwd(weights, jnp.asarray(seq[None],
                                                           jnp.int32)))
            out.append(np.stack(seen))
    finally:
        moe._routed = orig
    return np.stack(out)


def routing_diff(cfg: dict, weights, seqs) -> dict:
    """Share of (token, expert) choices that differ between the program
    and the float32 reference, and the reference's gap between its 6th
    and 7th probabilities where they do."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.refs import deepseek_v2 as ref

    prog = program_routing(cfg, weights, seqs)        # (n, L, S, k)
    k = prog.shape[-1]
    fwd = jax.jit(lambda w, t: ref.forward(cfg, w, t, t.shape[1] - 1,
                                           with_routing=True)[1])
    diff, total, margins, all_margins = 0, 0, [], []
    for i, seq in enumerate(seqs):
        want, margin = jax.device_get(fwd(weights, jnp.asarray(
            seq[None], jnp.int32)))
        want, margin = want[:, 0], margin[:, 0]        # (L, S, k), (L, S)
        all_margins.append(margin.ravel())
        for layer in range(want.shape[0]):
            for t in range(want.shape[1]):
                a, b = set(prog[i, layer, t]), set(want[layer, t])
                if a != b:
                    diff += len(b - a)
                    margins.append(float(margin[layer, t]))
        total += want.size
    all_margins = np.concatenate(all_margins)
    return {"routing.differ_share": diff / total,
            "routing.differ_token_layers": len(margins),
            "routing.token_layers": total // k,
            "routing.differ_margin_max": max(margins, default=0.0),
            "routing.margin_below_1pct": int(np.sum(all_margins < 0.01))}


def read_seed(c: dict, seed: int, seconds: float, devs,
              control: bool = True) -> dict:
    import importlib

    from bench.run import span
    from bench.runners import serve, serve_dsv2

    runner = importlib.import_module(
        f"bench.runners.{c['traffic']['runner']}")
    t0 = time.perf_counter()
    st = runner.setup(c["cfg"], c["traffic"], seed, devs)
    w = runner.window(st, seconds, span)
    row = {"seed": seed, "window_s": w["elapsed_s"], **w["e2e"],
           **{k: w["counters"][k] for k in ("routed_slots",
                                            "expert_layer_steps")}}
    prompts, served, _ = serve.collect(st, w,
                                       c["traffic"]["sample_requests"])
    weights = st.pop("weights")
    del st["eng"]
    st["log"].clear()
    gaps, seqs = serve_dsv2.served_gaps(c["cfg"], weights, prompts, served,
                                        c["limits"]["logit_gap_max"])
    row["logit_gap_max"] = float(gaps.max())
    row["gaps"] = [float(x) for x in gaps]
    if control:
        cg = serve_dsv2.control_gaps(c["cfg"], weights, seqs,
                                     c["traffic"]["prompt_tokens"])
        row["control.logit_gap_max"] = float(cg.max())
        row["control.gaps"] = [float(x) for x in cg]
        row.update(routing_diff(c["cfg"], weights, seqs))
    row["total_s"] = time.perf_counter() - t0
    return row


def plant_wrong_offset() -> None:
    """Tell the program's layer the next share's range of experts."""
    import dataclasses

    from bench.runners import serve_dsv2

    orig = serve_dsv2.model_config

    def shifted(cfg):
        m = orig(cfg)
        return dataclasses.replace(m, moe=dataclasses.replace(
            m.moe, expert_offset=m.moe.expert_offset + m.moe.held()))
    serve_dsv2.model_config = shifted


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=None,
                    help="run the control on the first N seeds only "
                         "(default: every seed)")
    ap.add_argument("--fault", choices=("offset",), default=None,
                    help="plant a fault in the program (see above)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench.run import load_cell
    from repro.core.engine import init_compile_cache

    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.fault == "offset":
        plant_wrong_offset()
    c = load_cell(args.workload)
    devs = jax.devices()[:c["cell"]["chips"]]
    seeds = [int(s) for s in args.seeds.split(",")]
    n_ctl = len(seeds) if args.control is None else args.control
    for i, seed in enumerate(seeds):
        row = read_seed(c, seed, args.seconds, devs, control=i < n_ctl)
        print(json.dumps({**row, "fault": args.fault}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
