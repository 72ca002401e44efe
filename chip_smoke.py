#!/usr/bin/env python3
"""Smoke test of the main path on one TPU chip: SLW training and serving of
gpt2-117m at its published widths, with random weights made from ``--seed``.

  python chip_smoke.py [--seed N]

Everything runs in this one process: a chip belongs to one process at a
time.  Each phase prints its own lines, and any failure exits non-zero.

* device — the first JAX device must be a TPU.  On anything else the script
  stops before any work and prints no result.
* train — the train CLI's own ``build_parser``/``build_config`` and
  ``Trainer`` at 8 x 1024, ``--remat full``, SLW from 8 tokens over the
  buckets 8, 264, 520 and 1024.  Every loss is finite, the first is near
  ln(vocab), and the flash-attention kernel is in the step program.
* parity — one training batch through ``attn_backend="flash"`` and
  ``"blockwise"``: loss and gradient norm agree within ``LOSS_RTOL`` and
  ``GRAD_NORM_RTOL``.
* serve — a Replica over the dense flash-decode kernel: 4 slots, cache
  1024, 8 greedy requests with prompts of 32 to 300 tokens.  One decode
  step's logits match ``decode_backend="reference"`` within ``LOGITS_RTOL``,
  the kernel is in the decode program, and every request is served.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every phase passed.  The wall times printed on the way are
a smoke test's clock, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from bench.harness.spans import CompileClock  # noqa: E402

ARCH = "gpt2-117m"
TRAIN_ARGV = ("--arch", ARCH, "--batch", "8", "--seq", "1024",
              "--remat", "full", "--slw", "--start-seq", "8",
              "--duration", "4", "--max-buckets", "4", "--steps", "7")
N_SLOTS, CACHE_LEN, GEN_TOKENS = 4, 1024, 32
PROMPT_LENS = (32, 300, 77, 150, 211, 45, 263, 128)

# Both sides of each parity check run in float32 with XLA's matmuls at
# "highest" precision, so what is left is the order of the f32 reductions:
# the kernels' online softmax against the reference's blocked or full one.
LOSS_RTOL = 1e-4
GRAD_NORM_RTOL = 1e-3
LOGITS_RTOL = 1e-3  # of the largest reference logit
FIRST_LOSS_ATOL = 0.5  # around ln(vocab), the loss of a uniform guess

KERNEL_OP = "tpu_custom_call"


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def global_norm(tree) -> float:
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(tree)
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                              for x in leaves)))


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def train_phase(seed: int, clock: CompileClock, dev):
    from repro.launch import train as train_cli

    args = train_cli.build_parser().parse_args(
        list(TRAIN_ARGV) + ["--seed", str(seed)])
    tc = train_cli.build_config(args)
    vocab = tc.model.vocab_size

    class StepLog(train_cli.TrainerHook):
        def __init__(self):
            self.rows = []

        def on_step_start(self, tr):
            self._t0 = time.perf_counter()
            self._c0 = clock.seconds

        def on_step_end(self, tr, tele, plan, metrics):
            # run_step has read the loss back, so the step has finished
            self.rows.append({"step": tele.step, "seq": plan.seq_len,
                              "batch": plan.batch_size, "loss": tele.loss,
                              "wall_s": time.perf_counter() - self._t0,
                              "compile_s": clock.seconds - self._c0})

    steplog = StepLog()
    trainer = train_cli.Trainer(tc, dp_size=1, hooks=[steplog])
    step_jit = trainer.step_fn
    last = {}

    def recording_step(state, batch, *a, **kw):
        last.update(batch=batch, a=a, kw=kw)
        return step_jit(state, batch, *a, **kw)

    trainer.step_fn = recording_step
    res = trainer.run()

    for r in steplog.rows:
        print(f"train step {r['step']}: seq={r['seq']} batch={r['batch']} "
              f"loss={r['loss']:.6f} wall_s={r['wall_s']:.3f} "
              f"compile_s={r['compile_s']:.3f}")
    buckets = {}
    for r in steplog.rows:
        buckets.setdefault(r["seq"], []).append(r)
    for seq, rows in buckets.items():
        print(f"train bucket seq={seq}: steps={len(rows)} "
              f"compile_s={sum(r['compile_s'] for r in rows):.3f} "
              f"first_step_wall_s={rows[0]['wall_s']:.3f}")
    full = buckets.get(tc.seq_len, [])
    print(f"train last steps at seq={tc.seq_len} (after its first): wall_s="
          f"{[round(r['wall_s'], 4) for r in full[1:]]}")
    print(f"train peak_bytes_in_use={peak_bytes(dev)}")

    losses = [r["loss"] for r in steplog.rows]
    check(len(losses) == tc.optimizer.total_steps,
          f"ran {len(losses)} of {tc.optimizer.total_steps} steps")
    check(not res.diverged and all(math.isfinite(x) for x in losses),
          f"non-finite loss: {losses}")
    check(abs(losses[0] - math.log(vocab)) <= FIRST_LOSS_ATOL,
          f"first loss {losses[0]:.4f} is not near ln({vocab})="
          f"{math.log(vocab):.4f}")
    seqs = sorted(buckets)
    check(len(seqs) >= 3 and seqs[0] < 128 and tc.seq_len in seqs
          and any(s % 128 for s in seqs if s >= 128),
          f"buckets {seqs} miss one below 128, one not a multiple of 128 "
          f"or the full {tc.seq_len}")

    hlo = step_jit.lower(trainer.state, last["batch"], *last["a"],
                         **last["kw"]).compile().as_text()
    check(KERNEL_OP in hlo, "no Pallas kernel in the compiled train step")
    print(f"train step program at seq={tc.seq_len}: {KERNEL_OP} found")
    return trainer, last["batch"]


def parity_phase(trainer, batch):
    import jax
    import jax.numpy as jnp
    from repro.models import model_zoo

    params = trainer.state["params"]
    got = {}
    with jax.default_matmul_precision("highest"):
        for backend in ("flash", "blockwise"):
            model = model_zoo.build_model(
                trainer.tc.model.replace(attn_backend=backend),
                dtype=jnp.float32, remat=trainer.tc.remat)
            fn = jax.jit(jax.value_and_grad(
                lambda p, b, m=model: m.loss(p, b)[0]))
            compiled = fn.lower(params, batch).compile()
            if backend == "flash":
                check(KERNEL_OP in compiled.as_text(),
                      "no Pallas kernel in the flash loss-and-grad program")
            loss, grads = compiled(params, batch)
            got[backend] = (float(loss), global_norm(grads))
    (lf, gf), (lb, gb) = got["flash"], got["blockwise"]
    dl, dg = rel_diff(lf, lb), rel_diff(gf, gb)
    shape = "x".join(map(str, batch["tokens"].shape))
    print(f"parity flash vs blockwise at {shape}: loss {lf:.8f} vs "
          f"{lb:.8f} rel_err={dl:.3e} (tol {LOSS_RTOL:g}); grad_norm "
          f"{gf:.8f} vs {gb:.8f} rel_err={dg:.3e} (tol {GRAD_NORM_RTOL:g})")
    check(math.isfinite(lf) and math.isfinite(gf), "non-finite parity run")
    check(dl <= LOSS_RTOL, f"loss rel_err {dl:.3e} > {LOSS_RTOL:g}")
    check(dg <= GRAD_NORM_RTOL, f"grad_norm rel_err {dg:.3e} > "
          f"{GRAD_NORM_RTOL:g}")


def serve_phase(seed: int, clock: CompileClock, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import SyntheticCorpus
    from repro.models import model_zoo
    from repro.serve import InferenceEngine, Request, SchedulerConfig

    c0 = clock.seconds
    sched = SchedulerConfig(n_slots=N_SLOTS, cache_len=CACHE_LEN,
                            min_prompt_bucket=16, round_multiple=32)
    eng = InferenceEngine.from_arch(ARCH, use_reduced=False, seed=seed,
                                    cfg=sched, decode_backend="kernel")
    cfg = eng.model.cfg
    prompts = np.asarray(SyntheticCorpus(
        vocab_size=cfg.vocab_size, seq_len=max(PROMPT_LENS),
        seed=seed).batch(0, len(PROMPT_LENS))["tokens"])
    reqs = [Request(uid=i, tokens=tuple(int(t) for t in prompts[i, :n]),
                    max_tokens=GEN_TOKENS)
            for i, n in enumerate(PROMPT_LENS)]
    for r in reqs:
        check(eng.try_submit(r), f"request {r.uid} was shed")
    while eng.admit():
        pass
    check(len(eng.scheduler.active) == N_SLOTS,
          f"{len(eng.scheduler.active)} of {N_SLOTS} slots admitted")

    # one decode step of the live slots, kernel against reference, on the
    # cache the admissions built (these jits do not donate it)
    toks = np.zeros((N_SLOTS, 1), np.int32)
    for slot, st in eng.scheduler.active.items():
        toks[slot, 0] = st.last_token
    ref_model = model_zoo.build_model(cfg.replace(decode_backend="reference"),
                                      dtype=jnp.float32, remat="none")
    with jax.default_matmul_precision("highest"):
        dec_kernel = jax.jit(eng.model.decode).lower(
            eng.params, eng.cache, toks).compile()
        dec_ref = jax.jit(ref_model.decode).lower(
            eng.params, eng.cache, toks).compile()
    check(KERNEL_OP in dec_kernel.as_text(),
          "no Pallas kernel in the compiled decode step")
    print(f"serve decode program: {KERNEL_OP} found")
    lk = np.asarray(dec_kernel(eng.params, eng.cache, toks)[0], np.float64)
    lr = np.asarray(dec_ref(eng.params, eng.cache, toks)[0], np.float64)
    err = float(np.max(np.abs(lk - lr)))
    scale = float(np.max(np.abs(lr)))
    lens = sorted(int(st.request.prompt_len)
                  for st in eng.scheduler.active.values())
    print(f"serve decode parity kernel vs reference (prompts {lens}): "
          f"max_abs_err={err:.3e} max_abs_logit={scale:.3e} "
          f"rel={err / max(scale, 1e-30):.3e} (tol {LOGITS_RTOL:g})")
    check(np.isfinite(lk).all(), "non-finite kernel logits")
    check(err <= LOGITS_RTOL * scale, "decode logits disagree")

    t0 = time.perf_counter()
    while eng.scheduler.busy:
        eng.pump()
    wall = time.perf_counter() - t0
    results = eng.take_finished()
    s = eng.stats
    reasons = {}
    for r in results:
        reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
    print(f"serve: {len(results)} results {reasons} slot_errors="
          f"{s.slot_errors} generated={s.generated_tokens} "
          f"decode_steps={s.decode_steps} prefill_s={s.prefill_s:.3f} "
          f"decode_s={s.decode_s:.3f} drain_wall_s={wall:.3f} "
          f"compile_s={clock.seconds - c0:.3f}")
    print(f"serve peak_bytes_in_use={peak_bytes(dev)}")
    check(len(results) == len(reqs), f"{len(results)} of {len(reqs)} served")
    check(s.slot_errors == 0, f"slot_errors={s.slot_errors}")
    for r in results:
        check(r.finish_reason not in ("error", "aborted", ""),
              f"request {r.uid} finished as {r.finish_reason!r}")
        check(len(r.tokens) == GEN_TOKENS,
              f"request {r.uid} generated {len(r.tokens)} tokens")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"device: the first device is {dev.platform!r}, not a TPU; "
              "nothing was run", file=sys.stderr)
        return 1
    count = len(jax.devices())
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={count}")
    print(f"compile cache: {cache_dir}")

    clock = CompileClock()
    t_start = time.perf_counter()
    phase = "train"
    try:
        trainer, batch = train_phase(args.seed, clock, dev)
        phase = "parity"
        parity_phase(trainer, batch)
        del trainer, batch
        phase = "serve"
        serve_phase(args.seed, clock, dev)
    except Exception:  # noqa: BLE001 — report which phase failed, exit 1
        traceback.print_exc()
        print(f"FAILED in phase {phase}", file=sys.stderr)
        return 1
    print(f"total: wall_s={time.perf_counter() - t_start:.1f} "
          f"compile_s={clock.seconds:.1f} persistent_cache_hits="
          f"{clock.cache_hits} misses={clock.cache_misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
