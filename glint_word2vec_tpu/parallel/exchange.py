"""Sparse touched-row delta exchange between data-parallel replicas.

ROADMAP item 4 (pod-scale training): the SPMD mesh path ships batch
payloads inside every jitted step, which is right for chips on one ICI
fabric — but across hosts on DCN (or across gang processes on gloo) the
win is to let each replica train privately on its own corpus shard and
reconcile on a cadence. SGNS touches O(batch * (1 + C + n)) rows per
step out of V, so reconciliation that ships whole tables (the classic
dense allreduce) pays O(V * d) per sync regardless of how little a
dispatch group actually trained. Following Ji et al. (arXiv:1604.04661)
and the partitioned-embedding work (arXiv:1909.03359), this module makes
the wire cost proportional to *touched rows* instead, and (ISSUE 16)
layers three independently-gated optimizations on that wire:

  * each replica snapshots its tables at group start (a jitted
    device-side copy — the train scans donate the live buffers, so the
    base costs one extra table pair of HBM, halved by bf16 storage);
  * after the dispatch group, a jitted diff+encode harvest dedupes
    touched rows BY CONSTRUCTION (one row = one delta, the table-diff
    restatement of the sorted-run-sum dedupe in ``engine._run_totals``)
    and compacts their ids into a FIXED-CAPACITY padded buffer via the
    same prefix-sum scatter trick as
    ``ops/device_batching.subsample_compact`` — every traced shape is
    constant, so the whole protocol compiles once and stays
    ``fit_stream``-compatible;
  * replicas allgather a tiny header, then the padded payload buffers;
  * every replica reconstructs ``base + delta_0 + delta_1 + ...`` in
    rank order with fp32 accumulation at the landing site (the PR 11
    discipline — bf16 tables round each row total once), so all
    replicas leave the sync with value-identical tables.

The ISSUE 16 wire layers (each with a parity escape hatch):

**Quantized deltas** (``wire="fp32"|"bf16"|"int8"``): bf16 halves the
payload by rounding each delta component once (decoded back to fp32
before accumulation); int8 ships a per-row symmetric maxabs scale plus
1-byte lanes and carries the quantization residual locally in an
error-feedback buffer — the residual folds into the next round's sent
rows, so the per-replica update *stream* stays unbiased even though
individual rounds are lossy. Every replica decodes the identical
``q * scale`` values, so replicas remain value-identical under any wire.
Residual carry is adopted only on rounds that actually shipped the
quantized payload (spill rounds ship exact fp32 deltas and leave the
carry untouched). ``flush()`` ships pending deltas *plus* the carry as
exact fp32 and zeroes the carry — the checkpoint hook that keeps
mid-run resume bitwise for a given (wire, R) config.

**Round coalescing** (``every=R``): ``group_end()`` counts dispatch
groups and runs a wire round only every R-th call — the base snapshot
simply stays put, so R groups of updates accumulate into one diff with
row dedup for free (zipf hot rows repeatedly touched in a window cost
one wire row). Drained replicas keep calling ``group_end(live=False,
done=True)``; every call advances the window, so boundary rounds stay
count-aligned across ranks and the lockstep collective never skews.

**Two-level topology-aware sync** (``topology="twolevel"``): Ji et
al. split the reconciliation across the bandwidth cliff — exact fp32
sparse payloads cross only the fast intra-node hop, node members fold
them into one node-level delta (deduped across the node's touched-row
union), and only node *leaders* ship the quantized node payload over
the slow inter-node hop (non-leaders contribute all-zero buffers whose
scatter adds an exact +0.0). Per-hop byte counters split
intra-node from inter-node traffic; over a flat gloo gang both hops
ride the same wire, so the split is a *model* of pod topology (real
deployments ride ICI for level 1) — documented caveat, see README.

**Adaptive capacity**: headers already carry each rank's true touched
counts, so every rank deterministically tracks the global high-water
mark over a rolling window and shrinks ``capacity`` (with 2x headroom
hysteresis) or grows it after an overflow spill — identical decisions
on identical headers, no extra wire. ``GLINT_EXCHANGE_CAPACITY`` (or an
explicit capacity) pins it.

**world=1 short-circuit**: a single replica reconciling with itself is
a no-op — ``sync`` skips the harvest and the wire entirely and records
``bytes=0`` (the MULTICHIP_BENCH world-1 artifact where sparse
"exceeded" dense). ``GLINT_EXCHANGE_FORCE_WIRE=1`` restores the old
loopback behavior for protocol unit tests.

Overflow spill: a group that touches more rows than ``capacity`` raises
the header's overflow flag and THAT round falls back to shipping the
dense per-rank delta (correctness never depends on the capacity guess);
``exchange_overflow_total`` counts the spills so operators can size
capacity from telemetry. ``GLINT_DENSE_EXCHANGE=1`` forces the dense
path outright (the escape hatch and the parity baseline).

Transports: :class:`ProcessTransport` rides
``jax.experimental.multihost_utils.process_allgather`` (gloo on CPU
gangs, DCN on pods); :class:`NullTransport` is the 1-replica degenerate
case; :func:`sync_group` drives N in-process engines through the same
decide/encode/apply helpers (the weak-scaling harness and the parity
tests).
"""

from __future__ import annotations

import os
import time
from collections import deque
from typing import List, Optional, Sequence

import numpy as np

from glint_word2vec_tpu.utils import faults, next_pow2

#: Wire dtype of exact (fp32-wire / dense / flush) delta payloads:
#: accumulation dtype, not storage dtype — deltas of bf16 tables still
#: travel and sum in fp32 so the reconstruction rounds each row total
#: once (same contract as ``engine._scatter_rows``).
_WIRE_DTYPE = np.float32

#: Supported sparse payload encodings (``--exchange-wire``).
WIRE_FORMATS = ("fp32", "bf16", "int8")

#: Header layout (int64): [live, done, n0, ovf0, n1, ovf1].
HEADER_LEN = 6

#: Adaptive capacity: boundary rounds of high-water history required
#: before a shrink is considered, and the smallest capacity adaptation
#: will ever pick (the same floor ``default_capacity`` uses).
CAPACITY_WINDOW = 16
CAPACITY_FLOOR = 256


def _wire_np_dtype(wire: str):
    """Host numpy dtype of the sparse payload lanes for one wire."""
    if wire == "bf16":
        import ml_dtypes  # ships with jax

        return np.dtype(ml_dtypes.bfloat16)
    if wire == "int8":
        return np.dtype(np.int8)
    return np.dtype(_WIRE_DTYPE)


def wire_row_bytes(wire: str, dim: int) -> int:
    """Wire cost of ONE sparse touched row: 4-byte id + payload lanes
    (+ the per-row fp32 scale for int8). The bench surface and the
    README variant matrix quote these."""
    if wire == "bf16":
        return 4 + 2 * dim
    if wire == "int8":
        return 4 + dim + 4
    return 4 + 4 * dim


def default_capacity(engine, pair_batch: int, steps_per_call: int) -> int:
    """Capacity heuristic: bound the rows one dispatch group can touch
    — ``steps_per_call * pair_batch`` pairs, each touching one center,
    one context, and ``num_negatives`` noise rows — rounded up to a
    power of two and clamped to the table. Dedup makes the true count
    far smaller on zipfian corpora; overflow spills keep a bad guess
    safe, not wrong, and the adaptive shrink walks it down toward the
    observed high-water mark. ``GLINT_EXCHANGE_CAPACITY`` overrides
    (and pins — no adaptation)."""
    env = os.environ.get("GLINT_EXCHANGE_CAPACITY")
    if env:
        return max(1, min(int(env), engine.num_rows))
    touched = pair_batch * steps_per_call * (2 + engine.num_negatives)
    return min(next_pow2(max(CAPACITY_FLOOR, touched)), engine.num_rows)


def _build_diff_fn(engine):
    """Jitted (cur, base) -> full-shape fp32 delta. Split out of the
    old monolithic harvest so the flat path, the two-level node
    accumulator, and every wire encoder share one diff program."""
    import jax
    import jax.numpy as jnp

    def diff(cur, base):
        return cur.astype(jnp.float32) - base.astype(jnp.float32)

    return jax.jit(diff)


def _build_encode_fn(engine, capacity: int, wire: str, flush: bool):
    """Jitted (delta, carry) -> ``(ids, payload, scales, n, overflow,
    new_carry, resid_abs)`` sparse encoder for one table.

    Touched = any component of the fp32 delta is nonzero (flush rounds
    also count rows with pending carry); ids compact into the
    ``capacity`` buffer by prefix-sum scatter (slot ``capacity`` is the
    shared dump slot for overflow/untouched writes).

    Wire behaviors:
      * fp32 — exact payload; carry passes through untouched.
      * bf16 — payload rounded to bfloat16 once (decoded to fp32 at the
        landing site); no error feedback (half-ULP of bf16).
      * int8 — error feedback: the pending carry folds into each SENT
        row, the sum quantizes to (int8 q, per-row fp32 maxabs scale),
        and ``new_carry`` holds exactly ``full - q*scale`` for sent
        rows (dump-slot scatter: unsent rows keep their carry, invalid
        slots write zeros to the dump row). The caller adopts
        ``new_carry`` only if the round actually ships this payload.
      * flush=True — exact fp32 payload of delta + carry with
        ``new_carry = 0``: the checkpoint flush that drains the error
        feedback state through the wire.

    ``carry`` has shape ``(num_rows + 1, dim)`` — the extra row is the
    scatter dump slot."""
    import jax
    import jax.numpy as jnp

    cap = int(capacity)  # graftlint: ignore[sync-point] host config scalar
    num_rows = engine.num_rows
    dim = engine.dim

    def encode(delta, carry):
        rows = jnp.arange(delta.shape[0], dtype=jnp.int32)
        if flush:
            eff = delta.at[:num_rows, :dim].add(carry[:num_rows])
            touched = jnp.any(eff != 0.0, axis=1) & (rows < num_rows)
        else:
            eff = delta
            touched = jnp.any(delta != 0.0, axis=1) & (rows < num_rows)
        n = touched.sum().astype(jnp.int32)
        pos = jnp.cumsum(touched.astype(jnp.int32)) - 1
        slot = jnp.where(touched & (pos < cap), pos, cap)
        ids = jnp.zeros(cap + 1, jnp.int32).at[slot].set(rows)[:cap]
        valid = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(n, cap)
        ids = jnp.where(valid, ids, 0)
        if flush:
            payload = jnp.where(valid[:, None], eff[ids, :dim], 0.0)
            scales = jnp.zeros(cap, jnp.float32)
            new_carry = jnp.zeros_like(carry)
            resid = jnp.float32(0.0)
        elif wire == "int8":
            full = delta[ids, :dim] + carry[ids]
            full = jnp.where(valid[:, None], full, 0.0)
            scale = jnp.max(jnp.abs(full), axis=1) / 127.0
            safe = jnp.where(scale > 0.0, scale, 1.0)
            q = jnp.clip(
                jnp.round(full / safe[:, None]), -127.0, 127.0
            ).astype(jnp.int8)
            deq = q.astype(jnp.float32) * scale[:, None]
            resid_rows = jnp.where(valid[:, None], full - deq, 0.0)
            dump = jnp.where(valid, ids, num_rows)
            new_carry = carry.at[dump].set(resid_rows)
            payload = q
            scales = scale
            resid = jnp.max(jnp.abs(new_carry))
        elif wire == "bf16":
            full = jnp.where(valid[:, None], delta[ids, :dim], 0.0)
            payload = full.astype(jnp.bfloat16)
            scales = jnp.zeros(cap, jnp.float32)
            new_carry = carry
            resid = jnp.max(jnp.abs(carry))
        else:  # fp32
            payload = jnp.where(valid[:, None], delta[ids, :dim], 0.0)
            scales = jnp.zeros(cap, jnp.float32)
            new_carry = carry
            resid = jnp.max(jnp.abs(carry))
        return ids, payload, scales, n, n > cap, new_carry, resid

    return jax.jit(encode)


def _build_dense_fn(engine):
    """Jitted (cur, base) -> fp32 delta sliced to the real
    (num_rows, dim) extent — the spill/dense-mode payload."""
    import jax
    import jax.numpy as jnp

    num_rows, dim = engine.num_rows, engine.dim

    def dense(cur, base):
        d = cur.astype(jnp.float32) - base.astype(jnp.float32)
        return d[:num_rows, :dim]

    return jax.jit(dense)


def _build_dense_carry_fn(engine):
    """Dense payload with the error-feedback carry folded in — the
    flush round's spill form (an exact superset of ``_build_dense_fn``:
    callers pass a zero carry to get the plain dense delta)."""
    import jax
    import jax.numpy as jnp

    num_rows, dim = engine.num_rows, engine.dim

    def dense(cur, base, carry):
        d = cur.astype(jnp.float32) - base.astype(jnp.float32)
        return d[:num_rows, :dim] + carry[:num_rows]

    return jax.jit(dense)


def _build_node_accum_fn(engine, capacity: int, members: tuple):
    """Jitted level-1 fold: scatter-add the exact fp32 sparse payloads
    of this rank's NODE MEMBERS (static tuple) into one dense node
    delta. Every member runs the identical program over the identical
    gathered buffers in the identical rank order, so all members hold
    the identical node delta (and hence the identical level-2 encoding
    and carry) without any extra coordination."""
    import jax
    import jax.numpy as jnp

    num_rows, dim = engine.num_rows, engine.dim

    def accum(ids_r, deltas_r):
        acc = jnp.zeros((num_rows, dim), jnp.float32)
        for r in members:
            acc = acc.at[ids_r[r]].add(deltas_r[r].astype(jnp.float32))
        return acc

    return jax.jit(accum)


def _build_apply_sparse_fn(engine, capacity: int, world: int, wire: str):
    """Jitted reconstruction ``base + sum_r decode(payload_r)`` from R
    stacked sparse payloads, applied rank by rank (ids unique within a
    rank, so every scatter is deterministic and each replica computes
    the identical float sum in the identical order). Decoding happens
    HERE, at the landing site, so accumulation is always fp32 no matter
    the wire (int8 lanes scale by their per-row fp32 maxabs scale; bf16
    lanes widen)."""
    import jax
    import jax.numpy as jnp

    dim = engine.dim
    tsh = engine._table_sharding()

    def one(base, ids_r, payload_r, scales_r):
        acc = base.astype(jnp.float32)
        for r in range(world):
            if wire == "int8":
                dec = payload_r[r].astype(jnp.float32) \
                    * scales_r[r][:, None]
            else:
                dec = payload_r[r].astype(jnp.float32)
            upd = jnp.zeros(
                (capacity, base.shape[1]), jnp.float32
            ).at[:, :dim].set(dec)
            acc = acc.at[ids_r[r]].add(upd)
        return acc.astype(base.dtype)

    def apply(base0, base1, ids0, p0, s0, ids1, p1, s1):
        return one(base0, ids0, p0, s0), one(base1, ids1, p1, s1)

    return jax.jit(apply, out_shardings=(tsh, tsh))


def _build_snapshot_fn(engine):
    """Jitted device-side table copy for the reconciliation base. A
    bare reference is NOT a snapshot here: the train scans donate the
    table buffers, so the pre-group arrays would be freed by the first
    dispatch. One extra table pair of HBM while an exchange group is in
    flight (bf16 storage halves it)."""
    import jax
    import jax.numpy as jnp

    tsh = engine._table_sharding()

    def snap(a, b):
        return jnp.copy(a), jnp.copy(b)

    return jax.jit(snap, out_shardings=(tsh, tsh))


def _build_apply_dense_fn(engine, world: int):
    """Dense twin of the sparse apply: sequential per-rank full-delta
    adds in rank order — per-row float schedule identical to the sparse
    scatter path (an untouched rank contributes exact +0.0)."""
    import jax
    import jax.numpy as jnp

    num_rows, dim = engine.num_rows, engine.dim
    tsh = engine._table_sharding()

    def one(base, deltas_r):
        acc = base.astype(jnp.float32)
        for r in range(world):
            pad = jnp.zeros(base.shape, jnp.float32)
            pad = pad.at[:num_rows, :dim].set(deltas_r[r])
            acc = acc + pad
        return acc.astype(base.dtype)

    def apply(base0, base1, d0, d1):
        return one(base0, d0), one(base1, d1)

    return jax.jit(apply, out_shardings=(tsh, tsh))


class NullTransport:
    """1-replica transport: allgather returns the local payload alone.
    Keeps the exchange protocol exercisable (and its telemetry live) in
    single-process fits and unit tests (with
    ``GLINT_EXCHANGE_FORCE_WIRE=1`` now that world=1 short-circuits)."""

    rank = 0
    world = 1

    def allgather(self, arr: np.ndarray) -> np.ndarray:
        return np.asarray(arr)[None]


class ProcessTransport:
    """Cross-process transport over the JAX distributed runtime
    (``distributed.allgather_host``): gloo between CPU gang processes,
    DCN across pod hosts. Every payload shape is fixed by construction,
    so each distinct buffer compiles one collective. bf16 payloads ride
    the wire as uint16 views — bit-identical lanes, and the collective
    only ever sees dtypes every backend supports."""

    def __init__(self):
        import jax

        self.rank = jax.process_index()
        self.world = jax.process_count()

    def allgather(self, arr: np.ndarray) -> np.ndarray:
        from glint_word2vec_tpu.parallel.distributed import (
            allgather_host,
        )

        bf16 = _wire_np_dtype("bf16")
        if arr.dtype == bf16:
            out = allgather_host(np.ascontiguousarray(arr).view(np.uint16))
            return out.view(bf16)
        return allgather_host(arr)


class ReplicaExchanger:
    """Drives the touched-row delta exchange for ONE replica engine.

    Lifecycle: ``begin()`` snapshots the table refs; the fit loop runs
    one dispatch group and calls ``group_end(live=..., done=...)``,
    which runs a wire round (``sync``) every ``every``-th call —
    harvest, swap encoded deltas with the peer replicas through
    ``transport``, reconstruct the reconciled tables on every replica,
    re-snapshot. Both return True while any replica still has work (the
    lockstep loop condition: a drained replica keeps calling
    ``group_end(live=False, done=True)`` with empty payloads until the
    whole gang reports done, so no collective is ever left waiting).
    ``flush()`` drains the error-feedback carry before a checkpoint;
    ``epoch_reset()`` rearms the window/done latches between epochs.
    """

    def __init__(self, engine, *, mode: str = "sparse",
                 capacity: Optional[int] = None,
                 transport=None, pair_batch: int = 1024,
                 steps_per_call: int = 16, wire: str = "fp32",
                 every: int = 1, topology: str = "flat",
                 node_size: Optional[int] = None):
        if mode not in ("sparse", "dense"):
            raise ValueError("exchange mode must be 'sparse' or 'dense'")
        if wire not in WIRE_FORMATS:
            raise ValueError(
                "exchange wire must be one of %s" % (WIRE_FORMATS,)
            )
        if int(every) < 1:  # graftlint: ignore[sync-point] host config scalar
            raise ValueError("exchange every must be >= 1")
        if topology not in ("flat", "twolevel"):
            raise ValueError("exchange topology must be flat|twolevel")
        self.engine = engine
        self.transport = transport if transport is not None else NullTransport()
        if os.environ.get("GLINT_DENSE_EXCHANGE", "0") == "1":
            mode = "dense"  # operator escape hatch
        self.mode = mode
        # Dense mode always ships exact fp32 full deltas; the wire
        # encoders only shape sparse rounds.
        self.wire = wire if mode == "sparse" else "fp32"
        self.every = int(every)  # graftlint: ignore[sync-point] host config scalar
        self.topology = topology if mode == "sparse" else "flat"
        env_ns = os.environ.get("GLINT_RANKS_PER_NODE")
        ns = int(node_size) if node_size else (int(env_ns) if env_ns else 0)  # graftlint: ignore[sync-point] host config scalar
        #: ranks per node for the two-level topology; 0/None = the whole
        #: gang is one node (single-host default: one leader speaks on
        #: the modeled slow hop).
        self.node_size = ns if ns > 0 else None
        #: capacity is PINNED (no adaptation) when the operator chose it
        #: — explicit param or the env override.
        self.capacity_pinned = bool(capacity) or bool(
            os.environ.get("GLINT_EXCHANGE_CAPACITY")
        )
        # graftlint: ignore[sync-point] host config scalar
        self.capacity = int(
            capacity if capacity
            else default_capacity(engine, pair_batch, steps_per_call)
        )
        self._hw = deque(maxlen=CAPACITY_WINDOW)
        self._fns = {}
        self._base = None
        self._carry = None          # lazy (carry0, carry1) device pair
        self._pending_carry = None  # encoder output awaiting adoption
        self._resid_abs = 0.0
        self._window = 0
        self._live_pending = False
        self._done_pending = False
        self._gang_live = True
        #: world=1 short-circuit (ISSUE 16 satellite): one replica
        #: reconciling with itself is a no-op — skip the wire, report
        #: bytes=0. Env restores the loopback wire for protocol tests.
        self.short_circuit = (
            self.transport.world == 1
            and os.environ.get("GLINT_EXCHANGE_FORCE_WIRE", "0") != "1"
        )
        # Snapshot NOW: the base must predate the first dispatch group,
        # or that group's deltas silently vanish from the exchange.
        # (Kept even under the short-circuit: sync_group() drives
        # NullTransport exchangers through the real protocol.)
        self.begin()

    # -- device programs (compiled once per engine/capacity) -----------

    def _fn(self, kind: str, builder, *args):
        key = (kind, *args)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = builder(self.engine, *args)
        return fn

    def begin(self) -> None:
        """Snapshot the reconciliation base: a jitted device-side copy
        of both tables (the train scans donate the live buffers, so a
        reference would dangle after the first dispatch)."""
        fn = self._fn("snapshot", _build_snapshot_fn)
        self._base = fn(self.engine.syn0, self.engine.syn1)

    def _carry_pair(self):
        """Lazy error-feedback residual state: one fp32 (num_rows+1,
        dim) buffer per table (the +1 row is the scatter dump slot).
        Engine-facing residual telemetry reads it via
        :meth:`residual_stats`."""
        if self._carry is None:
            import jax.numpy as jnp

            shape = (self.engine.num_rows + 1, self.engine.dim)
            self._carry = (
                jnp.zeros(shape, jnp.float32),
                jnp.zeros(shape, jnp.float32),
            )
        return self._carry

    def residual_stats(self) -> dict:
        """Host view of the error-feedback carry magnitude — the
        'residual carry state' the engine exposes on its exchange
        telemetry (set after each encoding round; zero before the first
        int8 round and right after a flush)."""
        return {"residual_abs": float(self._resid_abs)}  # graftlint: ignore[sync-point] host stat

    def _node_members(self, world: int, rank: int) -> tuple:
        """Static node membership for the two-level topology: ranks are
        grouped contiguously ``node_size`` at a time (the gang launcher
        numbers co-located processes contiguously); the node leader is
        the lowest rank in the group."""
        ns = self.node_size or world
        node = rank // ns
        return tuple(r for r in range(world) if r // ns == node)

    # -- harvest (the device->host seam) -------------------------------

    def harvest(self, *, flush: bool = False):
        """Run the jitted diff + wire-encode for this replica and bring
        the fixed-capacity buffers to host (the one device->host sync
        of the exchange; the transport needs host arrays). Returns
        ``(header_body, payload)`` where payload is
        ``(ids0, p0, s0, ids1, p1, s1)`` host arrays (payload lanes in
        the wire dtype, per-row scales for int8).

        Under ``topology="twolevel"`` the LOCAL hop always encodes
        exact fp32 (quantization and error feedback apply to the
        node-level stream at the inter-node hop — see ``sync``);
        ``flush=True`` encodes delta + carry exactly and stages a zero
        carry."""
        diff = self._fn("diff", _build_diff_fn)
        d0 = diff(self.engine.syn0, self._base[0])
        d1 = diff(self.engine.syn1, self._base[1])
        local_wire = self.wire
        if self.topology == "twolevel" and not flush:
            local_wire = "fp32"
        enc = self._fn(
            "encode", _build_encode_fn, self.capacity, local_wire,
            bool(flush),
        )
        c0, c1 = self._carry_pair()
        i0, p0, s0, n0, o0, nc0, r0 = enc(d0, c0)
        i1, p1, s1, n1, o1, nc1, r1 = enc(d1, c1)
        if self.topology != "twolevel" or flush:
            # flat path: the local encoding IS the wire encoding, so
            # its carry/residual are the ones to (maybe) adopt.
            self._pending_carry = (nc0, nc1)
            self._resid_abs = float(
                max(float(np.asarray(r0)), float(np.asarray(r1)))
            )
        payload = (
            np.asarray(i0), np.asarray(p0), np.asarray(s0),
            np.asarray(i1), np.asarray(p1), np.asarray(s1),
        )
        return (
            int(n0), int(np.asarray(o0)), int(n1), int(np.asarray(o1)),
        ), payload

    def _dense_delta(self, *, with_carry: bool = False):
        """Host fp32 per-rank deltas for a dense/spill round — full
        (num_rows, dim) per table. Part of the harvest seam: the dense
        wire payload is by definition a host copy of the table diff.
        ``with_carry`` folds the error-feedback carry in (the flush
        round's spill form)."""
        if with_carry:
            fn = self._fn("dense_carry", _build_dense_carry_fn)
            c0, c1 = self._carry_pair()
            return (
                np.asarray(fn(self.engine.syn0, self._base[0], c0)),
                np.asarray(fn(self.engine.syn1, self._base[1], c1)),
            )
        fn = self._fn("dense", _build_dense_fn)
        return (
            np.asarray(fn(self.engine.syn0, self._base[0])),
            np.asarray(fn(self.engine.syn1, self._base[1])),
        )

    def _empty_sparse(self, wire: Optional[str] = None):
        """All-zero sparse payload in the round's wire dtype (lockstep
        filler): zero ids scatter an exact +0.0 into row 0."""
        if wire is None:
            wire = "fp32" if self.topology == "twolevel" else self.wire
        cap, d = self.capacity, self.engine.dim
        wdt = _wire_np_dtype(wire)
        return (
            np.zeros(cap, np.int32), np.zeros((cap, d), wdt),
            np.zeros(cap, np.float32),
            np.zeros(cap, np.int32), np.zeros((cap, d), wdt),
            np.zeros(cap, np.float32),
        )

    def _empty_dense(self):
        v, d = self.engine.num_rows, self.engine.dim
        z = np.zeros((v, d), _WIRE_DTYPE)
        return z, z

    # -- coalescing / window bookkeeping --------------------------------

    def group_end(self, *, live: bool = True, done: bool = False) -> bool:
        """Account one dispatch group (or one drained-filler slot) and
        run a wire round at every ``every``-th call. Liveness/doneness
        latch across the window; every call advances it, so boundary
        rounds stay count-aligned across ranks no matter who drained
        first. Returns the latest gang-live verdict (True = keep
        looping)."""
        self._window += 1
        self._live_pending = self._live_pending or bool(live)
        self._done_pending = self._done_pending or bool(done)
        if self._window % self.every:
            return self._gang_live
        alive = self.sync(
            live=self._live_pending, done=self._done_pending,
            groups=self.every,
        )
        self._live_pending = False
        self._gang_live = alive
        return alive

    def flush(self) -> bool:
        """Checkpoint hook: drain the error-feedback carry through an
        exact fp32 wire round and zero it, so a resume from the
        checkpoint replays bitwise against the uninterrupted run. The
        go/no-go decision is pure config (int8 wire, multi-replica
        sparse mode) — identical on every rank, so the collective round
        inside never skews. No-op (returns False) otherwise."""
        if (self.short_circuit or self.mode != "sparse"
                or self.wire != "int8"):
            self._window = 0
            return False
        if self.topology == "twolevel":
            # carry is NODE-level state, identical on every member; only
            # the leader may ship it or the flush would add it
            # node_size times. Rank-derived, so still collective-safe.
            members = self._node_members(
                self.transport.world, self.transport.rank
            )
            if self.transport.rank != members[0]:
                self._carry = None
        self.sync(live=True, done=False, flush=True, groups=0)
        self._carry = None
        self._pending_carry = None
        self._resid_abs = 0.0
        self._window = 0
        return True

    def epoch_reset(self) -> None:
        """Rearm the window and the done/live latches after a gang
        drain — each epoch is its own lockstep generation."""
        self._window = 0
        self._live_pending = False
        self._done_pending = False
        self._gang_live = True

    def _adapt_capacity(self, max_n: int, overflowed: bool):
        """Header-driven capacity adaptation (every rank sees the same
        headers, so every rank takes the same decision): grow straight
        past an overflow's true touched count; shrink only after a full
        window of high-water marks sits below half the current
        capacity (2x headroom hysteresis). Returns "grow" | "shrink" |
        None for telemetry."""
        if self.capacity_pinned or self.mode != "sparse":
            return None
        limit = self.engine.num_rows
        if overflowed:
            new = min(next_pow2(max(max_n, CAPACITY_FLOOR)), limit)
            self._hw.clear()
            if new > self.capacity:
                self.capacity = new
                return "grow"
            return None
        self._hw.append(int(max_n))  # graftlint: ignore[sync-point] host header scalar
        if len(self._hw) == CAPACITY_WINDOW:
            target = min(
                max(CAPACITY_FLOOR, next_pow2(2 * max(self._hw))), limit
            )
            if target < self.capacity:
                self.capacity = target
                self._hw.clear()
                return "shrink"
        return None

    # -- the protocol ---------------------------------------------------

    def sync(self, *, live: bool = True, done: bool = False,
             flush: bool = False, groups: int = 1) -> bool:
        """One wire round. ``live``: this replica dispatched >=1 group
        since the last round (False = empty payload, lockstep filler).
        ``done``: this replica has no further groups this epoch.
        ``flush``: exact fp32 round that also drains the error-feedback
        carry (all ranks flush together by config). ``groups``: dispatch
        groups folded into this round (telemetry). Returns True while
        ANY replica is not done (keep looping)."""
        eng, tr = self.engine, self.transport
        if self.short_circuit:
            eng._note_exchange(
                bytes_sent=0, rows=0, overflow=False, dense=False,
                seconds=0.0, wire=self.wire, groups=int(groups),
                flush=False, world1_skip=True, intra_bytes=0,
                capacity=int(self.capacity),
            )
            return not done
        t0 = time.time()
        header = np.zeros(HEADER_LEN, np.int64)
        header[0], header[1] = int(live or flush), int(done)
        payload = None
        if live or flush:
            (n0, o0, n1, o1), payload = self.harvest(flush=flush)
            header[2:] = (n0, o0, n1, o1)
        faults.fire("exchange.pre_send")
        headers = tr.allgather(header)
        dense_round = decide_dense(self.mode, headers)
        sent = headers.nbytes // max(tr.world, 1)
        intra = 0
        wire_round = "fp32" if (dense_round or flush) else self.wire
        touched_ids = None
        cap = self.capacity
        max_n = int(max(headers[:, 2].max(), headers[:, 4].max()))
        if dense_round:
            if flush:
                d0, d1 = self._dense_delta(with_carry=True)
            elif live:
                d0, d1 = self._dense_delta()
            else:
                d0, d1 = self._empty_dense()
            deltas0 = tr.allgather(d0)
            deltas1 = tr.allgather(d1)
            sent += d0.nbytes + d1.nbytes
            fn = self._fn(
                "apply_dense", _build_apply_dense_fn, tr.world
            )
            syn0, syn1 = fn(*self._base, deltas0, deltas1)
            if flush:
                self._carry = None
        elif self.topology == "twolevel" and tr.world > 1 and not flush:
            syn0, syn1, hop = self._twolevel_round(payload, headers)
            sent += hop["intra"] + hop["inter"]
            intra = hop["intra"]
            wire_round = hop["wire"]
            dense_round = hop["dense"]
            touched_ids = hop["touched_ids"]
            max_n = max(max_n, hop["max_n"])
        else:
            if payload is None:
                payload = self._empty_sparse()
            i0, p0, s0, i1, p1, s1 = payload
            ids0, ps0 = tr.allgather(i0), tr.allgather(p0)
            ids1, ps1 = tr.allgather(i1), tr.allgather(p1)
            sent += i0.nbytes + p0.nbytes + i1.nbytes + p1.nbytes
            if wire_round == "int8":
                sc0, sc1 = tr.allgather(s0), tr.allgather(s1)
                sent += s0.nbytes + s1.nbytes
            else:
                sc0 = np.zeros((tr.world, cap), np.float32)
                sc1 = sc0
            fn = self._fn(
                "apply_sparse", _build_apply_sparse_fn, cap, tr.world,
                wire_round,
            )
            syn0, syn1 = fn(
                *self._base, ids0, ps0, sc0, ids1, ps1, sc1
            )
            touched_ids = np.unique(
                np.concatenate([ids0.ravel(), ids1.ravel()])
            )
            if flush:
                self._carry = None
            elif self.wire == "int8" and live:
                self._carry = self._pending_carry
        eng.exchange_adopt(syn0, syn1, touched_ids=touched_ids)
        self.begin()
        cap_event = self._adapt_capacity(
            max_n, bool((headers[:, 3] | headers[:, 5]).any())
        )
        eng._note_exchange(
            bytes_sent=int(sent),
            rows=int(header[2] + header[4]),
            overflow=bool(header[3] or header[5]),
            dense=bool(dense_round),
            seconds=time.time() - t0,
            wire=wire_round,
            groups=int(groups),
            flush=bool(flush),
            world1_skip=False,
            intra_bytes=int(intra),
            capacity=int(self.capacity),
            cap_event=cap_event,
            residual_abs=float(self._resid_abs),
        )
        return not bool(headers[:, 1].all())

    def _twolevel_round(self, payload, headers):
        """Level 1 + level 2 of a two-level sparse round (called from
        the ``sync`` seam; all host/device traffic here is the same
        reconciliation barrier). Exact fp32 local payloads cross the
        intra-node hop; members fold them into the node delta; the
        node delta re-encodes under the configured wire with the NODE
        carry; leaders alone ship it inter-node (non-leaders gather
        zero buffers). Returns the reconciled tables plus per-hop byte
        attribution."""
        tr, cap = self.transport, self.capacity
        if payload is None:
            payload = self._empty_sparse("fp32")
        i0, p0, s0, i1, p1, s1 = payload
        g_i0, g_p0 = tr.allgather(i0), tr.allgather(p0)
        g_i1, g_p1 = tr.allgather(i1), tr.allgather(p1)
        intra = i0.nbytes + p0.nbytes + i1.nbytes + p1.nbytes
        members = self._node_members(tr.world, tr.rank)
        leader = tr.rank == members[0]
        acc = self._fn("node_accum", _build_node_accum_fn, cap, members)
        nd0 = acc(g_i0, g_p0)
        nd1 = acc(g_i1, g_p1)
        enc = self._fn(
            "encode", _build_encode_fn, cap, self.wire, False
        )
        c0, c1 = self._carry_pair()
        ni0, np0, ns0, nn0, no0, nc0, nr0 = enc(nd0, c0)
        ni1, np1, ns1, nn1, no1, nc1, nr1 = enc(nd1, c1)
        h2 = np.zeros(HEADER_LEN, np.int64)
        h2[2:] = (
            int(nn0), int(np.asarray(no0)),
            int(nn1), int(np.asarray(no1)),
        )
        h2s = tr.allgather(h2)
        inter = h2s.nbytes // max(tr.world, 1)
        max_n = int(max(h2s[:, 2].max(), h2s[:, 4].max()))
        if bool((h2s[:, 3] | h2s[:, 5]).any()):
            # node-union spill: leaders ship the dense node delta (an
            # exact fp32 payload), carry stays put for the next round.
            if leader:
                d0, d1 = np.asarray(nd0), np.asarray(nd1)
            else:
                d0, d1 = self._empty_dense()
            deltas0 = tr.allgather(d0)
            deltas1 = tr.allgather(d1)
            inter += (d0.nbytes + d1.nbytes) if leader else 0
            fn = self._fn(
                "apply_dense", _build_apply_dense_fn, tr.world
            )
            syn0, syn1 = fn(*self._base, deltas0, deltas1)
            return syn0, syn1, {
                "intra": int(intra), "inter": int(inter),
                "wire": "fp32", "dense": True, "touched_ids": None,
                "max_n": max_n,
            }
        if leader:
            out = (
                np.asarray(ni0), np.asarray(np0), np.asarray(ns0),
                np.asarray(ni1), np.asarray(np1), np.asarray(ns1),
            )
        else:
            out = self._empty_sparse(self.wire)
        li0, lp0, ls0, li1, lp1, ls1 = out
        ids0, ps0 = tr.allgather(li0), tr.allgather(lp0)
        ids1, ps1 = tr.allgather(li1), tr.allgather(lp1)
        if leader:
            inter += li0.nbytes + lp0.nbytes + li1.nbytes + lp1.nbytes
        if self.wire == "int8":
            sc0, sc1 = tr.allgather(ls0), tr.allgather(ls1)
            if leader:
                inter += ls0.nbytes + ls1.nbytes
        else:
            sc0 = np.zeros((tr.world, cap), np.float32)
            sc1 = sc0
        fn = self._fn(
            "apply_sparse", _build_apply_sparse_fn, cap, tr.world,
            self.wire,
        )
        syn0, syn1 = fn(*self._base, ids0, ps0, sc0, ids1, ps1, sc1)
        if self.wire == "int8":
            self._carry = (nc0, nc1)
            self._resid_abs = float(
                max(float(np.asarray(nr0)), float(np.asarray(nr1)))
            )
        touched_ids = np.unique(
            np.concatenate([ids0.ravel(), ids1.ravel()])
        )
        return syn0, syn1, {
            "intra": int(intra), "inter": int(inter),
            "wire": self.wire, "dense": False,
            "touched_ids": touched_ids, "max_n": max_n,
        }


def decide_dense(mode: str, headers: np.ndarray) -> bool:
    """Spill rule shared by the transported and in-process drivers: a
    round is dense when the configured mode says so, the escape hatch
    forces it, or ANY replica overflowed its capacity buffer."""
    if os.environ.get("GLINT_DENSE_EXCHANGE", "0") == "1":
        return True
    return mode == "dense" or bool((headers[:, 3] | headers[:, 5]).any())


def sync_group(exchangers: Sequence[ReplicaExchanger], *,
               live: Optional[List[bool]] = None,
               flush: bool = False) -> dict:
    """In-process N-replica exchange round: harvest every replica,
    decide sparse vs dense with the same spill rule, reconstruct every
    replica's tables in the same rank order — the single-process driver
    the weak-scaling harness and the parity tests run replicas through
    (each replica is its own engine; the "wire" is process memory, but
    payload bytes are counted exactly as the transported protocol
    ships them). Mirrors ``ReplicaExchanger.sync`` across every wire
    format, the two-level topology (replica list index = rank), flush
    rounds, and the header-driven capacity adaptation."""
    world = len(exchangers)
    ex0 = exchangers[0]
    mode, wire, topo = ex0.mode, ex0.wire, ex0.topology
    cap = ex0.capacity
    if live is None:
        live = [True] * world
    t0 = time.time()
    headers = np.zeros((world, HEADER_LEN), np.int64)
    payloads = []
    for r, ex in enumerate(exchangers):
        headers[r, 0] = int(live[r] or flush)
        if live[r] or flush:
            (n0, o0, n1, o1), p = ex.harvest(flush=flush)
            headers[r, 2:] = (n0, o0, n1, o1)
            payloads.append(p)
        else:
            payloads.append(None)
    faults.fire("exchange.pre_send")
    dense_round = decide_dense(mode, headers)
    wire_round = "fp32" if (dense_round or flush) else wire
    max_n = int(max(headers[:, 2].max(), headers[:, 4].max()))
    hdr_bytes = headers[0].nbytes
    intra_by_rank = [0] * world
    inter_by_rank = [0] * world
    touched_ids = None
    if dense_round:
        deltas = [
            ex._dense_delta(with_carry=flush) if (live[r] or flush)
            else ex._empty_dense()
            for r, ex in enumerate(exchangers)
        ]
        d0 = np.stack([d[0] for d in deltas])
        d1 = np.stack([d[1] for d in deltas])
        for r in range(world):
            inter_by_rank[r] = hdr_bytes + d0[r].nbytes + d1[r].nbytes
        apply_args = [("apply_dense", (_build_apply_dense_fn, world),
                       (d0, d1))]
    elif topo == "twolevel" and world > 1:
        # level 1 (intra hop): exact fp32 local payloads.
        ps = [
            p if p is not None else ex._empty_sparse("fp32")
            for p, ex in zip(payloads, exchangers)
        ]
        ids0 = np.stack([p[0] for p in ps])
        ps0 = np.stack([p[1] for p in ps])
        ids1 = np.stack([p[3] for p in ps])
        ps1 = np.stack([p[4] for p in ps])
        l1 = ids0[0].nbytes + ps0[0].nbytes \
            + ids1[0].nbytes + ps1[0].nbytes
        for r in range(world):
            intra_by_rank[r] = l1
        # level 2: fold + re-encode once per node (every member would
        # compute the identical result; the leader's engine does it).
        h2 = np.zeros((world, HEADER_LEN), np.int64)
        node_enc = {}   # leader rank -> host sparse payload
        node_nd = {}    # leader rank -> device node deltas (for spill)
        node_carry = {}  # leader rank -> (nc0, nc1, resid_abs)
        for r, ex in enumerate(exchangers):
            members = ex._node_members(world, r)
            if r != members[0]:
                continue
            acc = ex._fn("node_accum", _build_node_accum_fn, cap, members)
            nd0, nd1 = acc(ids0, ps0), acc(ids1, ps1)
            enc = ex._fn("encode", _build_encode_fn, cap, wire, False)
            c0, c1 = ex._carry_pair()
            ni0, q0, sc0, nn0, no0, nc0, nr0 = enc(nd0, c0)
            ni1, q1, sc1, nn1, no1, nc1, nr1 = enc(nd1, c1)
            row = (
                int(nn0), int(np.asarray(no0)),
                int(nn1), int(np.asarray(no1)),
            )
            for m in members:
                h2[m, 2:] = row
            node_enc[r] = (
                np.asarray(ni0), np.asarray(q0), np.asarray(sc0),
                np.asarray(ni1), np.asarray(q1), np.asarray(sc1),
            )
            node_nd[r] = (nd0, nd1)
            node_carry[r] = (
                nc0, nc1,
                max(float(np.asarray(nr0)), float(np.asarray(nr1))),
            )
        max_n = max(max_n, int(max(h2[:, 2].max(), h2[:, 4].max())))
        if bool((h2[:, 3] | h2[:, 5]).any()):
            # node-union spill: leaders ship dense node deltas.
            dense_round = True
            wire_round = "fp32"
            rows0, rows1 = [], []
            for r, ex in enumerate(exchangers):
                members = ex._node_members(world, r)
                if r == members[0]:
                    nd0, nd1 = node_nd[r]
                    a, b = np.asarray(nd0), np.asarray(nd1)
                    inter_by_rank[r] = hdr_bytes + a.nbytes + b.nbytes
                else:
                    a, b = ex._empty_dense()
                    inter_by_rank[r] = hdr_bytes
                rows0.append(a)
                rows1.append(b)
            apply_args = [("apply_dense", (_build_apply_dense_fn, world),
                           (np.stack(rows0), np.stack(rows1)))]
        else:
            outs = []
            for r, ex in enumerate(exchangers):
                members = ex._node_members(world, r)
                if r == members[0]:
                    out = node_enc[r]
                    inter_by_rank[r] = hdr_bytes + out[0].nbytes \
                        + out[1].nbytes + out[3].nbytes + out[4].nbytes
                    if wire == "int8":
                        inter_by_rank[r] += out[2].nbytes + out[5].nbytes
                else:
                    out = ex._empty_sparse(wire)
                    inter_by_rank[r] = hdr_bytes
                outs.append(out)
            gi0 = np.stack([o[0] for o in outs])
            gq0 = np.stack([o[1] for o in outs])
            gs0 = np.stack([o[2] for o in outs])
            gi1 = np.stack([o[3] for o in outs])
            gq1 = np.stack([o[4] for o in outs])
            gs1 = np.stack([o[5] for o in outs])
            touched_ids = np.unique(
                np.concatenate([gi0.ravel(), gi1.ravel()])
            )
            apply_args = [("apply_sparse",
                           (_build_apply_sparse_fn, cap, world, wire),
                           (gi0, gq0, gs0, gi1, gq1, gs1))]
            for r, ex in enumerate(exchangers):
                if wire == "int8":
                    leader = ex._node_members(world, r)[0]
                    nc0, nc1, resid = node_carry[leader]
                    ex._carry = (nc0, nc1)
                    ex._resid_abs = resid
    else:
        ps = [
            p if p is not None else ex._empty_sparse(wire_round)
            for p, ex in zip(payloads, exchangers)
        ]
        ids0 = np.stack([p[0] for p in ps])
        q0 = np.stack([p[1] for p in ps])
        sc0 = np.stack([p[2] for p in ps])
        ids1 = np.stack([p[3] for p in ps])
        q1 = np.stack([p[4] for p in ps])
        sc1 = np.stack([p[5] for p in ps])
        per = ids0[0].nbytes + q0[0].nbytes + ids1[0].nbytes + q1[0].nbytes
        if wire_round == "int8":
            per += sc0[0].nbytes + sc1[0].nbytes
        for r in range(world):
            inter_by_rank[r] = hdr_bytes + per
        touched_ids = np.unique(
            np.concatenate([ids0.ravel(), ids1.ravel()])
        )
        apply_args = [("apply_sparse",
                       (_build_apply_sparse_fn, cap, world, wire_round),
                       (ids0, q0, sc0, ids1, q1, sc1))]
        for r, ex in enumerate(exchangers):
            if flush:
                ex._carry = None
            elif wire == "int8" and live[r]:
                ex._carry = ex._pending_carry
    kind, builder_args, args = apply_args[0]
    overflowed = bool((headers[:, 3] | headers[:, 5]).any())
    cap_event = None
    for r, ex in enumerate(exchangers):
        t1 = time.time()
        fn = ex._fn(kind, *builder_args)
        syn0, syn1 = fn(*ex._base, *args)
        ex.engine.exchange_adopt(syn0, syn1, touched_ids=touched_ids)
        ex.begin()
        cap_event = ex._adapt_capacity(max_n, overflowed)
        ex.engine._note_exchange(
            bytes_sent=int(intra_by_rank[r] + inter_by_rank[r]),
            rows=int(headers[r, 2] + headers[r, 4]),
            overflow=bool(headers[r, 3] or headers[r, 5]),
            dense=bool(dense_round),
            seconds=time.time() - t1,
            wire=wire_round,
            groups=1,
            flush=bool(flush),
            world1_skip=False,
            intra_bytes=int(intra_by_rank[r]),
            capacity=int(ex.capacity),
            cap_event=cap_event,
            residual_abs=float(ex._resid_abs),
        )
    return {
        "dense": bool(dense_round),
        "bytes_per_rank": int(
            sum(intra_by_rank[r] + inter_by_rank[r]
                for r in range(world)) // world
        ),
        "intra_bytes_per_rank": int(sum(intra_by_rank) // world),
        "inter_bytes_per_rank": int(sum(inter_by_rank) // world),
        "wire": wire_round,
        "capacity": int(exchangers[0].capacity),
        "cap_event": cap_event,
        "seconds": time.time() - t0,
        "rows": [int(headers[r, 2] + headers[r, 4]) for r in range(world)],
    }


def flush_group(exchangers: Sequence[ReplicaExchanger]) -> bool:
    """In-process twin of ``ReplicaExchanger.flush``: drain every
    replica's error-feedback carry through one exact fp32 round (the
    pre-checkpoint hook in tests and the weak-scaling harness). No-op
    unless the config actually accumulates a carry (int8 sparse)."""
    ex0 = exchangers[0]
    if ex0.mode != "sparse" or ex0.wire != "int8":
        for ex in exchangers:
            ex._window = 0
        return False
    world = len(exchangers)
    if ex0.topology == "twolevel":
        for r, ex in enumerate(exchangers):
            if r != ex._node_members(world, r)[0]:
                ex._carry = None  # node carry ships once, via the leader
    sync_group(exchangers, flush=True)
    for ex in exchangers:
        ex._carry = None
        ex._pending_carry = None
        ex._resid_abs = 0.0
        ex._window = 0
    return True
