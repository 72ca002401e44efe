"""Time the four flash-attention kernels for candidate tiles on a TPU.

    python experiments/flash_tiles.py [--out tiles.json]

For each sequence length and tile ``(block_h, block_q, block_k)`` this
compiles the forward and backward kernels at the flattened ``(BH, S_pad,
D)`` layout that ``ops.py`` hands them (``S_pad`` the length padded to the
tile), runs them ``--reps`` times under one profiler trace, and reads each
kernel's device seconds per call from the trace (``flash_attention_fwd``,
``_delta``, ``_dq``, ``_dkv``).  Tiles the chip's compiler refuses (too
much VMEM) are listed with the refusal.  Besides the per-tile rows the JSON
holds, for each kernel, a least-squares split of its time over all timed
tiles into a fixed cost per grid step, a cost per live (head, tile)
product and a cost per computed score (``fit``).

Off a TPU the kernels run in interpret mode and no device times exist; use
``--small`` there to check the script itself.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import glob
import itertools
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import resolve_interpret  # noqa: E402
from repro.kernels.flash_attention.kernel import (  # noqa: E402
    VMEM_BUDGET, _first_q_block, _last_kv_block, flash_attention_bwd,
    flash_attention_fwd, vmem_bytes)

KERNELS = ("flash_attention_fwd", "flash_attention_delta",
           "flash_attention_dq", "flash_attention_dkv")
SPAN = "tiles."


def candidates(seqs, heads, short_heads, blocks, d, itemsize, square=False):
    """(s, block_h, block_q, block_k) to time.  Blocks clamp to ``s``;
    tiles that clamp to the same blocks are timed once, and tiles whose
    two blocks pad ``s`` further than the larger block alone are left out,
    as are head groups that need twice the VMEM the chooser allows.
    Sequences that fit one block take ``short_heads``."""
    seen = set()
    for s in seqs:
        hs = short_heads if s <= min(blocks) else heads
        for h, bq, bk in itertools.product(hs, blocks, blocks):
            bq, bk = min(bq, s), min(bk, s)
            if (math.lcm(bq, bk) != max(bq, bk) or (s, h, bq, bk) in seen
                    or (square and bq != bk)
                    or vmem_bytes(h, bq, bk, d, itemsize) > 2 * VMEM_BUDGET):
                continue
            seen.add((s, h, bq, bk))
            yield s, h, bq, bk


def grid_counts(bh, s, h, bq, bk):
    """Per kernel, at the padded length ``ops.py`` uses: grid steps per
    call, live (head, tile) products, and the scores they compute with
    the lanes padded to 128 (for delta: no tiles, and rows for scores)."""
    s_pad = s + (-s) % math.lcm(bq, bk)
    qb, kb = s_pad // bq, s_pad // bk
    steps = bh // h * qb * kb
    live_q = bh * sum(_last_kv_block(q, bq, bk) + 1 for q in range(qb))
    live_k = bh * sum(qb - _first_q_block(k, bq, bk) for k in range(kb))
    tile = -(-bq // 8) * 8 * -(-bk // 128) * 128
    return s_pad, {
        "flash_attention_fwd": (steps, live_q, live_q * tile),
        "flash_attention_dq": (steps, live_q, live_q * tile),
        "flash_attention_dkv": (steps, live_k, live_k * tile),
        "flash_attention_delta": (bh // h * qb, 0, bh * s_pad),
    }


def build(bh, s, d, dtype, h, bq, bk, interpret):
    """Compiled forward and backward at one tile."""
    s_pad, _ = grid_counts(bh, s, h, bq, bk)
    x = jax.ShapeDtypeStruct((bh, s_pad, d), dtype)
    rows = jax.ShapeDtypeStruct((bh, 1, s_pad), jnp.float32)
    kw = dict(block_h=h, block_q=bq, block_k=bk, valid_len=s,
              interpret=interpret)
    fwd = jax.jit(lambda q, k, v: flash_attention_fwd(q, k, v, **kw))
    bwd = jax.jit(lambda q, k, v, o, lse, do: flash_attention_bwd(
        q, k, v, o, lse, do, **kw))
    return (fwd.lower(x, x, x).compile(),
            bwd.lower(x, x, x, x, rows, x).compile())


def device_seconds(xplane):
    """Per span name: {kernel: device seconds} of the custom calls that
    started inside the span (device times put on the host's clock)."""
    from bench.harness.trace import DEVICE_PREFIX, clock_shift, split_hlo
    data = jax.profiler.ProfileData.from_file(xplane)
    ops, modules, launches, host = [], [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(split_hlo(ev.name)[0], ev.start_ns,
                             ev.duration_ns) for ev in line.events]
                elif line.name == "XLA Modules":
                    modules += [ev.start_ns for ev in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
                    elif ev.name == "PJRT_LoadedExecutable_Execute":
                        launches.append(ev.start_ns)
    shift = clock_shift(modules, launches)
    out = {}
    for name, lo, hi in host:
        tot = dict.fromkeys(KERNELS, 0.0)
        for op, start, dur in ops:
            kern = op.rsplit(".", 1)[0]
            if kern in tot and lo <= start + shift <= hi:
                tot[kern] += dur * 1e-9
        out[name] = tot
    return out


def fit(rows, bh):
    """Per kernel, least squares of seconds per call on the three counts of
    ``grid_counts``, in relative error, over the tiles of sequences longer
    than 128: microseconds per grid step, per live (head, tile) product
    and per thousand scores, and the worst relative residual."""
    res = {}
    for kern in KERNELS:
        a, y = [], []
        for r in rows:
            if r["s"] <= 128:
                continue
            t = r.get("s_per_call", {}).get(kern)
            if t:
                a.append(grid_counts(bh, r["s"], r["block_h"], r["block_q"],
                                     r["block_k"])[1][kern])
                y.append(t)
        if len(y) < 4:
            continue
        a, y = np.array(a, float), np.array(y, float)
        coef, *_ = np.linalg.lstsq(a / y[:, None], np.ones_like(y),
                                   rcond=None)
        res[kern] = {"us_per_step": coef[0] * 1e6,
                     "us_per_head_tile": coef[1] * 1e6,
                     "us_per_k_scores": coef[2] * 1e9,
                     "worst_rel_residual":
                         float(np.max(np.abs(a @ coef / y - 1))),
                     "n": len(y)}
    return res


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bh", type=int, default=384)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--seqs", default="8,136,520,1024")
    ap.add_argument("--heads", default="1,2,4,8,16")
    ap.add_argument("--short-heads", default="1,2,4,8,16,32,64,128",
                    help="head groups for sequences of one block")
    ap.add_argument("--blocks", default="128,256,512")
    ap.add_argument("--square", action="store_true",
                    help="only tiles with block_q == block_k")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes (bh 4, d 16, seqs 8,40, blocks 8,16)")
    ap.add_argument("--out", help="also write the rows and fit as JSON")
    args = ap.parse_args()
    if args.small:
        args.bh, args.d, args.seqs, args.heads, args.short_heads, \
            args.blocks = 4, 16, "8,40", "1,2,4", "1,4", "8,16"
    ints = lambda s: [int(x) for x in s.split(",")]
    dtype = jnp.dtype(args.dtype)
    interpret = resolve_interpret(None)
    cands = [c for c in candidates(ints(args.seqs), ints(args.heads),
                                   ints(args.short_heads), ints(args.blocks),
                                   args.d, dtype.itemsize, args.square)
             if args.bh % c[1] == 0]

    def compile_one(c):
        s, h, bq, bk = c
        try:
            return c, build(args.bh, s, args.d, dtype, h, bq, bk, interpret)
        except Exception as e:  # noqa: BLE001 — the refusal is the result
            return c, str(e).splitlines()[0][:240]

    t0 = time.time()
    with concurrent.futures.ThreadPoolExecutor(args.workers) as pool:
        built = list(pool.map(compile_one, cands))
    print(f"compiled {len(built)} tiles in {time.time() - t0:.1f} s",
          flush=True)

    key = jax.random.PRNGKey(0)
    inputs = {}
    rows = []
    tmp = tempfile.mkdtemp(prefix="flash-tiles-")
    jax.profiler.start_trace(tmp)
    try:
        for i, ((s, h, bq, bk), got) in enumerate(built):
            s_pad, counts = grid_counts(args.bh, s, h, bq, bk)
            row = {"s": s, "s_pad": s_pad, "block_h": h, "block_q": bq,
                   "block_k": bk,
                   "steps": {k: v[0] for k, v in counts.items()}}
            rows.append(row)
            if isinstance(got, str):
                row["refused"] = got
                continue
            fwd, bwd = got
            if s_pad not in inputs:
                ks = jax.random.split(jax.random.fold_in(key, s_pad), 4)
                x = [jax.random.normal(k_, (args.bh, s_pad, args.d), dtype)
                     for k_ in ks]
                inputs[s_pad] = x
            q, k, v, do = inputs[s_pad]
            o, lse = fwd(q, k, v)
            jax.block_until_ready(bwd(q, k, v, o, lse, do))
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"{SPAN}{i}"):
                for _ in range(args.reps):
                    o, lse = fwd(q, k, v)
                    out = bwd(q, k, v, o, lse, do)
                jax.block_until_ready(out)
            row["host_s_per_rep"] = (time.perf_counter() - t) / args.reps
            time.sleep(0.01)  # keep the spans apart on the device's clock
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    if files:
        secs = device_seconds(max(files, key=os.path.getmtime))
        for i, row in enumerate(rows):
            got = secs.get(f"{SPAN}{i}")
            if got and any(got.values()):
                row["s_per_call"] = {k: v / args.reps for k, v in got.items()}
    shutil.rmtree(tmp, ignore_errors=True)

    result = {"device": str(jax.devices()[0].device_kind), "bh": args.bh,
              "d": args.d, "dtype": dtype.name, "reps": args.reps,
              "rows": rows, "fit": fit(rows, args.bh)}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    for r in rows:
        t = r.get("s_per_call")
        cols = " ".join(f"{t[k] * 1e3:8.3f}" for k in KERNELS) if t else \
            r.get("refused", f"host {r.get('host_s_per_rep', 0):.4f} s")
        print(f"S {r['s']:5d} pad {r['s_pad']:5d} h {r['block_h']:3d} "
              f"q {r['block_q']:4d} k {r['block_k']:4d}  ms/call "
              f"fwd delta dq dkv: {cols}", flush=True)
    print("fit", json.dumps(result["fit"]), flush=True)


if __name__ == "__main__":
    main()
