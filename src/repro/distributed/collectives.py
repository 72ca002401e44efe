"""shard_map collectives: flash-decoding over a sequence-sharded KV cache.

For the ``long_500k`` decode cells the KV cache (or attention over a long
context generally) is sharded along the *sequence* axis across the ``data``
mesh axis.  Plain SPMD would all-gather the cache to every device
(seq_len * kv * head_dim bytes — the collective term explodes).  The
flash-decoding formulation computes a *partial* softmax per shard and merges
(max, sum-exp, weighted-value) triples with three tiny collectives — bytes
proportional to B*H*D instead of B*S*KV*D.

Masking convention — **pos = count of valid entries** (cache row ``j`` is
valid iff ``j < pos``), shared with ``models.attention.decode_attention``
and the flash-decode kernel.  The per-shard partial is the same
``(o, m, l)`` triple the kernel emits
(``kernels.flash_decode.ops.flash_decode_partials``), so the sharded merge
can consume kernel partials directly: ``backend="kernel"`` runs the Pallas
split-KV kernel inside each shard instead of the jnp local term.

This is the beyond-paper §Perf lever for the decode-bound cells.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.kernels import resolve_backend
from repro.kernels.flash_decode.ops import flash_decode_partials
from repro.kernels.flash_decode.ref import (decode_attention_reference,
                                            decode_partials_reference)


def flash_decode_sharded(mesh: Mesh, seq_axis: str = "data",
                         backend: str = "reference"):
    """Returns fn(q, k_cache, v_cache, pos) -> out.

    q: (B, 1, H, D) replicated over `seq_axis`;
    k_cache/v_cache: (B, S, KV, D) sharded along S over `seq_axis`;
    pos: () int32, count of valid cache entries (global).

    ``backend`` selects the per-shard partial: "reference" (jnp oracle),
    "kernel" (Pallas flash-decode kernel, compiled on TPU / reference
    fallback elsewhere) or "kernel_interpret" (kernel in interpret mode —
    the CPU validation path).
    """
    use_kernel, interpret = resolve_backend(backend, "decode backend")

    def local(q, k, v, pos):
        b, sq, h, d = q.shape
        assert sq == 1, "flash decode serves one token per step"
        s_local = k.shape[1]
        shard = jax.lax.axis_index(seq_axis)
        base = shard * s_local  # global position of this shard's first entry
        # count of valid entries inside this shard (empty shards yield
        # (o, m, l) = (0, NEG_INF, 0) and drop out of the merge exactly)
        lengths = jnp.broadcast_to(
            jnp.clip(pos - base, 0, s_local), (b,)).astype(jnp.int32)
        if use_kernel:
            o, m, l = flash_decode_partials(q[:, 0], k, v, lengths,
                                            interpret=interpret)
        else:
            o, m, l = decode_partials_reference(q[:, 0], k, v, lengths)
        # merge partial softmaxes across shards
        gm = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - gm)
        l_tot = jax.lax.psum(l * corr, seq_axis)
        o_tot = jax.lax.psum(o * corr[..., None], seq_axis)
        out = o_tot / jnp.maximum(l_tot[..., None], 1e-30)
        return out.reshape(b, sq, h, d).astype(q.dtype)

    def apply(q, k_cache, v_cache, pos):
        kv_spec = P(None, seq_axis, None, None)
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(), kv_spec, kv_spec, P()),
            out_specs=P(),
            check_vma=False)(q, k_cache, v_cache, pos)

    return apply


def reference_decode(q, k_cache, v_cache, pos):
    """Unsharded oracle for flash_decode_sharded (pos = count of valid
    entries, scalar or per-row (B,) vector)."""
    b, sq, h, d = q.shape
    assert sq == 1, "flash decode serves one token per step"
    lengths = jnp.broadcast_to(jnp.asarray(pos), (b,)).astype(jnp.int32)
    out = decode_attention_reference(q[:, 0], k_cache, v_cache, lengths)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def migrate_row(src_state, src_cache, src_slot, dst_state, dst_cache,
                dst_slot, cache_len=None, placement=None):
    """Move one slot's cache row between two DecodeStates (slot migration).

    The row travels in *model format* — ``gather`` on the source, optional
    seq-capacity ``fit_row`` + cross-host/mesh ``device_put``, ``insert``
    on the destination, ``evict`` on the source — so it works across dense
    and paged states in either direction (a paged gather returns
    ``pages_per_slot * page_size`` seq entries; ``fit_row`` trims/pads to
    the destination geometry, lossless because everything past ``pos`` is
    garbage the target never reads).  This is the single-host half of the
    disaggregated-serving story: the prefill→decode handoff and the
    router's replica rebalancing both ride this path, and the ``placement``
    hook is where a multi-host destination mesh plugs in.

    Returns the updated ``(src_cache, dst_cache)``; host bookkeeping
    (scheduler slot state, page reservations) is the caller's job —
    see ``Replica.migrate_slot_to``.
    """
    row = src_state.gather(src_cache, src_slot)
    if cache_len is not None:
        row = dst_state.fit_row(row, cache_len)
    if placement is not None:
        row = jax.device_put(row, placement)
    dst_cache = dst_state.insert(dst_cache, dst_slot, row)
    src_cache = src_state.evict(src_cache, src_slot)
    return src_cache, dst_cache
