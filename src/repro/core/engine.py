"""CollectiveEngine — the CCLO: executes microcode schedules on a TPU mesh.

Mirrors the ACCL+ hardware split (§4.4):

  control plane  = Python at trace time: the selector picks an algorithm,
                   the generator emits a Schedule (microcode), the compiler
                   lowers it to a micro-op Program — the uC + DMP.
  data plane     = ONE executor, `execute_program`, interpreting the fixed
                   micro-op set (core/program.py) as XLA: `collective-
                   permute` ops (Tx/Rx systems), dynamic slices (RxBuf
                   manager placement), combine ops / codecs (streaming
                   plugins).

Every collective — ring, tree, hypercube, masked, compressed, segmented —
lowers through the same executor; there are no per-algorithm hand-written
lowerings. That is the paper's property: new collectives are new
microprograms, not new circuits. Uniform step runs (rings) execute as one
rolled lax.scan (the LOOP micro-op), keeping O(n)-step schedules at O(1)
live buffers; segmented uniform runs execute as ONE skewed scan over
segment waves (the STREAM micro-op — the CCLO's hop-to-hop pipelining,
§4.4.3); O(log n) schedules (trees, hypercubes) unroll.

All MPI-like methods are called *inside* a `shard_map` region (the engine's
H2H role inside train/serve steps) or via `run()` which wraps one for
standalone use (the F2F role). `backend='native'` lowers to XLA's built-in
collectives instead — the "software MPI" baseline of the paper's figures.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import hierarchical, plugins, telemetry
from repro.core.algorithms import GENERATORS
from repro.core.program import (
    SRC_BUFFER, SRC_ORIGINAL, Copy, Compress, Decompress, Loop, Program,
    RecvCombine, SegLoop, Send, StackedRecv, Stream, StreamChain,
    _overlaps, _regions_stream_safe, fit_segments, split_exchange,
)
from repro.core.schedule import (
    SEL_ALL, SEL_CHUNK, SEL_MASK, SEL_RANGE, Schedule, Sel,
)
from repro.core.selector import Selector
from repro.core.topology import (
    Communicator, ProductComm, axis_comm, product_comm,
)
from repro.core.hw_spec import HwSpec, hw_for_devices


# --------------------------------------------------------------------------
# Region helpers (RxBuf manager placement)
# --------------------------------------------------------------------------

def _select(buf, chunks: int, sel: Sel, rank, s_idx):
    csize = buf.shape[0] // chunks
    if sel.kind == SEL_ALL:
        return buf
    if sel.kind == SEL_CHUNK:
        idx = sel.fn(rank, s_idx)
        return lax.dynamic_slice_in_dim(buf, idx * csize, csize, 0)
    if sel.kind == SEL_RANGE:
        off, length = sel.fn(rank, s_idx)
        return lax.dynamic_slice_in_dim(buf, off * csize, int(length) * csize, 0)
    if sel.kind == SEL_MASK:
        idxs = sel.fn(rank, s_idx)
        return jnp.concatenate(
            [buf[j * csize:(j + 1) * csize] for j in idxs], axis=0)
    raise ValueError(sel.kind)


def _recv_region(buf, chunks: int, sel: Sel, rank, s_idx):
    """(view, elem_offset, mask_idxs) of the region `recv_sel` writes.

    elem_offset is None for SEL_ALL (the whole buffer); mask_idxs is the
    static chunk-index tuple for SEL_MASK (the view is their gathered
    concatenation) and None otherwise."""
    csize = buf.shape[0] // chunks
    if sel.kind == SEL_MASK:
        idxs = sel.fn(rank, s_idx)
        view = jnp.concatenate(
            [buf[j * csize:(j + 1) * csize] for j in idxs], axis=0)
        return view, None, tuple(idxs)
    if sel.kind == SEL_ALL:
        return buf, None, None
    if sel.kind == SEL_CHUNK:
        off = sel.fn(rank, s_idx) * csize
    else:
        off = sel.fn(rank, s_idx)[0] * csize
    return _select(buf, chunks, sel, rank, s_idx), off, None


@telemetry.named_scope("uop.combine")
def _apply_write(buf, chunks: int, off, mask_idxs, new_val):
    """Write a combined region value back (inverse of `_recv_region`)."""
    if mask_idxs is not None:
        csize = buf.shape[0] // chunks
        for k, j in enumerate(mask_idxs):
            buf = buf.at[j * csize:(j + 1) * csize].set(
                new_val[k * csize:(k + 1) * csize])
        return buf
    if off is None:
        return new_val
    return lax.dynamic_update_slice_in_dim(buf, new_val, off, 0)


def _chunk_roll(buf, chunks: int, shift, reverse: bool = False):
    """Local chunk rotation (the Bruck pre/post COPY micro-ops)."""
    csize = buf.shape[0] // chunks
    grp = buf.reshape((chunks, csize) + buf.shape[1:])
    if reverse:
        grp = grp[::-1]
    grp = jnp.roll(grp, shift, axis=0)
    return grp.reshape(buf.shape)


# --------------------------------------------------------------------------
# Wire pipeline (SEG_LOOP / COMPRESS / SEND / DECOMPRESS)
# --------------------------------------------------------------------------

def _fit_segments(seg_len: int, segments) -> int:
    """Largest k <= segments that divides seg_len (>= 1); see
    `program.fit_segments` (this alias keeps the historical name used by
    the streaming fusions and tests)."""
    return fit_segments(seg_len, segments)


def _split_wire(mid_ops: tuple):
    """Split the wire micro-ops at the SEND: ([COMPRESS?, SEND], [DECOMPRESS?]).

    The send half runs at transmit time; the decompress half runs at
    *consume* time, directly feeding the combine plugin. Keeping the
    dequantize multiply adjacent to the combine add in every context —
    straight-line k=1, inside the SEG_LOOP scan body, and the pipeline
    tail — means XLA's FMA contraction fires identically everywhere, so
    segmented codec wires stay bitwise-equal to unsegmented ones (the
    per-segment scale-reuse guarantee). It also shrinks the pipeline's
    in-flight state to the compressed wire format.
    """
    for i, op in enumerate(mid_ops):
        if isinstance(op, Send):
            return mid_ops[:i + 1], mid_ops[i + 1:]
    raise ValueError("exchange without a SEND op")


def _send_axis(op: Send, axis):
    """(mesh axis, permutation) one SEND ppermutes on.

    A flat execution passes `axis` as the axis NAME and every SEND uses
    its flat-rank perm. A two-level execution passes a dict
    {"inter": outer_axis, "intra": inner_axis}: each SEND then permutes
    its level-local perm on its level's own mesh axis (a single-axis
    ppermute replicates across the orthogonal axis — exactly the
    per-pod / per-slot replication the composed schedule encodes in its
    flat perms)."""
    if isinstance(axis, dict):
        if op.level is None:
            raise ValueError(
                "flat (level=None) SEND inside a two-axis execution — "
                "only hierarchical programs run on an axis dict")
        return axis[op.level], op.level_perm
    return axis, op.perm


def _send_chain(send_ops: tuple, seg, axis, use_pallas: bool):
    """[COMPRESS?] SEND — payload in, (possibly compressed) arrival out."""
    cur = seg
    for op in send_ops:
        if isinstance(op, Compress):
            with jax.named_scope("uop.codec"):
                cur = plugins.get_codec(op.codec).compress(
                    cur, use_pallas=use_pallas)
        elif isinstance(op, Send):
            ax, perm = _send_axis(op, axis)
            cur = jax.tree.map(
                lambda leaf, a=ax, p=perm: lax.ppermute(leaf, a, p), cur)
        else:
            raise ValueError(f"bad send op {op}")
    return cur


def _recv_chain(dec_ops: tuple, wire, shape, dtype, use_pallas: bool):
    """[DECOMPRESS?] — arrived wire format in, payload-dtype segment out."""
    cur = wire
    for op in dec_ops:
        if isinstance(op, Decompress):
            with jax.named_scope("uop.codec"):
                cur = plugins.get_codec(op.codec).decompress(
                    cur, shape, dtype, use_pallas=use_pallas)
        else:
            raise ValueError(f"bad recv op {op}")
    return cur


def _pipelined_exchange(payload, send, consume, segments: int,
                        collect_raw: bool = False):
    """Double-buffered segmented exchange: the ACCL+ Rx-buffer pipeline.

    Splits `payload` (leading dim divisible by `segments`) into segments,
    puts segment 0 on the wire, then runs an inner lax.scan whose body
    launches segment s+1 with `send` while `consume(s, incoming_s)`
    combines/places the segment already in flight — so the wire and the
    combine plugin run concurrently, exactly the §4.4.3 Tx/Rx pipelining.

    send:    seg -> in-flight seg (the transmit chain; may be a compressed
             wire-format pytree).
    consume: (seg_index, in-flight seg) -> output seg when `collect_raw`
             is False, else (output seg, raw decompressed arrival). Must
             be jax-traceable with a traced index; decompression happens
             here so the dequantize feeds the combine directly in every
             context (see `_split_wire`).
    Returns (outputs, raw_incomings) stacked back to the full step payload;
    raw_incomings is None unless `collect_raw` (relay='received' needs the
    uncombined arrivals as the next step's payload).
    """
    k = int(segments)
    if k <= 1:
        res = consume(0, send(payload))
        return res if collect_raw else (res, None)
    pay = payload.reshape((k, payload.shape[0] // k) + payload.shape[1:])
    inflight = send(pay[0])

    def seg_body(carry, i):
        nxt = send(pay[i + 1])          # segment i+1 rides the wire ...
        out = consume(i, carry)         # ... while segment i is combined
        return nxt, out

    last, outs = lax.scan(seg_body, inflight, jnp.arange(k - 1))
    tail = consume(k - 1, last)

    def _stack(stacked, tail_leaf):
        return jnp.concatenate(
            [stacked.reshape((-1,) + stacked.shape[2:]), tail_leaf], axis=0)

    if not collect_raw:
        return _stack(outs, tail), None
    return _stack(outs[0], tail[0]), _stack(outs[1], tail[1])


# --------------------------------------------------------------------------
# The executor (the DMP): one path for every collective
# --------------------------------------------------------------------------

def _codec_block(mid_ops: tuple) -> int:
    for op in mid_ops:
        if isinstance(op, Compress):
            return plugins.get_codec(op.codec).block_elems
    return 1


def _exchange_update(body: tuple, k_req: int, buf, orig, prev, chunks: int,
                     rank, step, axis: str, use_pallas: bool):
    """Compute one exchange's region update WITHOUT writing it.

    body = (Copy('load'), [Compress], Send, [Decompress], RecvCombine).
    Returns (off, mask_idxs, new_val, raw_incoming) — the caller applies
    the write (immediately for unrolled steps, deferred to iteration end
    inside a LOOP)."""
    load, recv = body[0], body[-1]
    send_ops, dec_ops = _split_wire(body[1:-1])
    src = {"buffer": buf, "original": orig, "received": prev}[load.source]
    payload = _select(src, chunks, load.sel, rank, step)
    view, off, mask_idxs = _recv_region(buf, chunks, recv.sel, rank, step)

    k = 1
    if k_req > 1 and view.shape[0] == payload.shape[0]:
        row_elems = max(1, payload.size // max(1, payload.shape[0]))
        # per-segment scale reuse: segment boundaries never straddle a
        # codec scale block, so segmented codec wires stay bitwise equal
        # to unsegmented ones
        k = fit_segments(payload.shape[0], k_req, row_elems,
                         _codec_block(send_ops))

    comb = functools.partial(plugins.combine, recv.op,
                             use_pallas=use_pallas)
    is_dst = None
    if recv.dsts is not None:
        is_dst = jnp.any(rank == jnp.asarray(recv.dsts))

    seg_shape = ((payload.shape[0] // k,) + payload.shape[1:])
    tgt = view.reshape((k, -1) + view.shape[1:])

    def send(seg):
        return _send_chain(send_ops, seg, axis, use_pallas)

    def consume(i, wire):
        inc = _recv_chain(dec_ops, wire, seg_shape, payload.dtype,
                          use_pallas)
        with jax.named_scope("uop.combine"):
            out = comb(tgt[i], inc.astype(buf.dtype))
        return (out, inc) if recv.track_recv else out

    new_val, raw = _pipelined_exchange(payload, send, consume, k,
                                       collect_raw=recv.track_recv)
    new_val = new_val.reshape(view.shape)
    if raw is not None:
        raw = raw.reshape(payload.shape)
    if is_dst is not None:
        new_val = jnp.where(is_dst, new_val, view)
    return off, mask_idxs, new_val, raw


def _exec_loop(loop: Loop, buf, orig, prev, chunks: int, rank, axis: str,
               use_pallas: bool):
    """Rolled execution of a uniform step run — ONE lax.scan, one live
    buffer. Slot payloads and combine targets read the iteration-start
    buffer (region writes land at iteration end), so the slots' permutes
    carry no intra-iteration data dependency and XLA schedules them on
    independent links concurrently (the bidirectional ring)."""
    track = any(split_exchange(s)[0][-1].track_recv for s in loop.slots)
    carry0 = (buf, prev) if track else buf

    def body(carry, i):
        b, pv = carry if track else (carry, prev)
        writes = []
        new_prev = pv
        for slot, seq in enumerate(loop.slots):
            step = loop.base + i * loop.period + slot
            ops, k_req = split_exchange(seq)
            off, mask_idxs, new_val, raw = _exchange_update(
                ops, k_req, b, orig, pv, chunks, rank, step, axis,
                use_pallas)
            writes.append((off, mask_idxs, new_val))
            if raw is not None:
                new_prev = raw
        for off, mask_idxs, new_val in writes:
            b = _apply_write(b, chunks, off, mask_idxs, new_val)
        return ((b, new_prev) if track else b), None

    out, _ = lax.scan(body, carry0, jnp.arange(loop.trip))
    return out if track else (out, prev)


def _exec_stream(st: Stream, buf, orig, prev, chunks: int, nranks: int,
                 rank, axis: str, use_pallas: bool):
    """Cross-step segment streaming: ONE skewed scan over trip*k waves.

    Wave g holds segment (iteration g//k, segment g%k) in flight for every
    slot: the wave body first launches wave g+1's payloads (read from the
    pre-consume carry) and then combines wave g's arrivals — so step s+1's
    segment 0 rides the wire before step s's tail segment combines, the
    hop-to-hop pipelining SEG_LOOP's per-step scan barrier cannot reach.
    Segment g+1's payload depends at most on segment g+1-k's combine
    (k >= 2 keeps that strictly in the past), and eligible region shapes
    (see `program._stream_eligible`) make the single out-of-order tail
    send read only untouched data — the streamed program is bitwise-equal
    to its unfused form.
    """
    csize = buf.shape[0] // chunks
    parts = []
    for body in st.slots:
        load, recv = body[0], body[-1]
        send_ops, dec_ops = _split_wire(body[1:-1])
        parts.append((load, send_ops, dec_ops, recv))

    # Static segment fit — the same clamp as the unfused SEG_LOOP path,
    # applied jointly so every slot streams at one wave rate.
    k = st.segments
    pay_len = None
    for (load, send_ops, _dec, recv) in parts:
        src0 = {"buffer": buf, "original": orig, "received": prev}[
            load.source]
        pay0 = _select(src0, chunks, load.sel, rank, st.base)
        row_elems = max(1, pay0.size // max(1, pay0.shape[0]))
        k = min(k, fit_segments(pay0.shape[0], k, row_elems,
                                _codec_block(send_ops)))
        if pay_len is None:
            pay_len = pay0.shape[0]
        elif pay_len != pay0.shape[0]:
            k = 1  # slots disagree on the wave size: stream degenerates
    if k >= 2 and k != st.segments and any(
            SEL_RANGE in (b[0].sel.kind, b[-1].sel.kind)
            for b in st.slots):
        # SEL_RANGE eligibility was PROVEN at the requested segment
        # count, and the proof is k-dependent (the head segment grows as
        # k shrinks): a trace-time clamp must re-run it at the admitted
        # count — the chunk/original/received rules hold at any k >= 2
        # and need no re-proof. Range runs are period-1 by eligibility.
        load0, recv0 = st.slots[0][0], st.slots[0][-1]
        seq = [(load0.sel, recv0.sel, load0.source, st.base + i)
               for i in range(st.trip)]
        if not _regions_stream_safe(seq, k, nranks):
            k = 1  # unproven at the clamped count: drop to rolled form
    if k < 2:
        loop = Loop(base=st.base, trip=st.trip, period=st.period,
                    slots=tuple((SegLoop(st.segments, b),)
                                for b in st.slots))
        return _exec_loop(loop, buf, orig, prev, chunks, rank, axis,
                          use_pallas)
    seg_len = pay_len // k
    dtype = buf.dtype

    def send_wave(m, b, pv, i, j):
        load, send_ops, _dec, _recv = parts[m]
        src = {"buffer": b, "original": orig, "received": pv}[load.source]
        step = st.base + i * st.period + m
        region = _select(src, chunks, load.sel, rank, step)
        seg = lax.dynamic_slice_in_dim(region, j * seg_len, seg_len, 0)
        return _send_chain(send_ops, seg, axis, use_pallas)

    def consume_wave(m, b, pv, wire, i, j):
        _load, _send, dec_ops, recv = parts[m]
        step = st.base + i * st.period + m
        if recv.sel.kind == SEL_ALL:
            off = j * seg_len
        elif recv.sel.kind == SEL_CHUNK:
            off = recv.sel.fn(rank, step) * csize + j * seg_len
        else:  # SEL_RANGE (proven by _regions_stream_safe)
            off = recv.sel.fn(rank, step)[0] * csize + j * seg_len
        tgt = lax.dynamic_slice_in_dim(b, off, seg_len, 0)
        inc = _recv_chain(dec_ops, wire, (seg_len,) + b.shape[1:], dtype,
                          use_pallas)
        with jax.named_scope("uop.combine"):
            out = plugins.combine(recv.op, tgt, inc.astype(dtype),
                                  use_pallas=use_pallas)
            b = lax.dynamic_update_slice_in_dim(b, out, off, 0)
            if recv.track_recv:
                pv = lax.dynamic_update_slice_in_dim(pv, inc, j * seg_len,
                                                     0)
        return b, pv

    nslots = len(parts)
    waves = st.trip * k
    infl0 = tuple(send_wave(m, buf, prev, 0, 0) for m in range(nslots))

    def wave(carry, g):
        b, pv, infl = carry
        i, j = g // k, g % k
        i1, j1 = (g + 1) // k, (g + 1) % k
        # launch wave g+1 from the pre-consume state, THEN combine wave g
        nxt = tuple(send_wave(m, b, pv, i1, j1) for m in range(nslots))
        for m in range(nslots):
            b, pv = consume_wave(m, b, pv, infl[m], i, j)
        return (b, pv, nxt), None

    (buf, prev, infl), _ = lax.scan(wave, (buf, prev, infl0),
                                    jnp.arange(waves - 1))
    for m in range(nslots):  # drain: the tail segment of the last step
        buf, prev = consume_wave(m, buf, prev, infl[m], st.trip - 1, k - 1)
    return buf, prev


def _chain_elem_off(sel: Sel, r, step, csize: int):
    """Element offset of a contiguous (chunk/range) selector region."""
    if sel.kind == SEL_CHUNK:
        return sel.fn(r, step) * csize
    return sel.fn(r, step)[0] * csize


def _chain_clamp_safe(plan, csize: int, nranks: int) -> bool:
    """Re-verify the region-overlap proof at the segment counts the
    payloads ACTUALLY admit (element units, per concrete rank).

    `fuse_chains` proved the chain at the requested segment count;
    `fit_segments` may have clamped a step's count down at trace time
    (indivisible payload, codec scale blocks), which changes the wave
    schedule — e.g. a clamp to k=2 re-creates the head/tail overlap the
    compile-time proof excluded. Payloads read from the immutable
    original buffer skip the read-side checks, as in the compiler pass.
    """
    try:
        for r in range(nranks):
            regions = []
            for (load, _s_ops, _d_ops, recv, pay, k) in plan:
                step = load.step
                s_off = int(_chain_elem_off(load.sel, r, step, csize))
                r_off = int(_chain_elem_off(recv.sel, r, step, csize))
                if load.source == SRC_BUFFER and _overlaps(
                        s_off, s_off + pay, r_off, r_off + pay):
                    return False
                regions.append((load.source, s_off, pay, k, r_off))
            for i in range(1, len(regions)):
                source, s_off, pay, k, _r_off = regions[i]
                if source != SRC_BUFFER:
                    continue
                _src0, _so0, pay0, k0, r_off0 = regions[i - 1]
                if _overlaps(s_off, s_off + pay // k,
                             r_off0 + pay0 - pay0 // k0, r_off0 + pay0):
                    return False
    except Exception:
        return False
    return True


def _exec_chain(ch: StreamChain, buf, orig, prev, chunks: int, nranks: int,
                rank, axis: str, use_pallas: bool):
    """Cross-step segment streaming over distinct unrolled steps: the
    wave sequence [(step, segment)] executed with a skew of one — wave
    w+1's payload goes on the wire (read from the pre-combine buffer)
    before wave w's arrival runs through the combine plugin, so step
    s+1's head segment crosses the Tx/Rx system during step s's tail
    combine. Unrolled (log-step runs are short); each step keeps its own
    admitted segment count, and if trace-time clamping invalidates the
    compile-time region proof the chain falls back to per-step SEG_LOOP
    execution — bitwise-equal either way.
    """
    csize = buf.shape[0] // chunks
    row_elems = 1
    for d in buf.shape[1:]:
        row_elems *= int(d)
    plan = []
    for body in ch.bodies:
        load, recv = body[0], body[-1]
        send_ops, dec_ops = _split_wire(body[1:-1])
        ln = 1 if load.sel.kind == SEL_CHUNK \
            else int(load.sel.fn(0, load.step)[1])
        pay = ln * csize
        k = fit_segments(pay, ch.segments, row_elems,
                         _codec_block(send_ops))
        plan.append((load, send_ops, dec_ops, recv, pay, k))

    if not _chain_clamp_safe(plan, csize, nranks):
        for body in ch.bodies:  # per-step fallback: plain SEG_LOOP order
            off, mask_idxs, new_val, _raw = _exchange_update(
                body, ch.segments, buf, orig, prev, chunks, rank,
                body[0].step, axis, use_pallas)
            buf = _apply_write(buf, chunks, off, mask_idxs, new_val)
        return buf

    dtype = buf.dtype
    waves = [(s, j) for s in range(len(plan))
             for j in range(plan[s][5])]

    def send_wave(b, s, j):
        load, send_ops, _dec, _recv, pay, k = plan[s]
        src = orig if load.source == SRC_ORIGINAL else b
        off = _chain_elem_off(load.sel, rank, load.step, csize)
        seg = lax.dynamic_slice_in_dim(src, off + j * (pay // k),
                                       pay // k, 0)
        return _send_chain(send_ops, seg, axis, use_pallas)

    def consume_wave(b, wire, s, j):
        _load, _send, dec_ops, recv, pay, k = plan[s]
        seg = pay // k
        off = _chain_elem_off(recv.sel, rank, recv.step, csize) + j * seg
        tgt = lax.dynamic_slice_in_dim(b, off, seg, 0)
        inc = _recv_chain(dec_ops, wire, (seg,) + b.shape[1:], dtype,
                          use_pallas)
        with jax.named_scope("uop.combine"):
            out = plugins.combine(recv.op, tgt, inc.astype(dtype),
                                  use_pallas=use_pallas)
            return lax.dynamic_update_slice_in_dim(b, out, off, 0)

    inflight = send_wave(buf, *waves[0])
    for w, (s, j) in enumerate(waves):
        # launch wave w+1 from the pre-consume buffer, THEN combine w
        nxt = send_wave(buf, *waves[w + 1]) if w + 1 < len(waves) else None
        buf = consume_wave(buf, inflight, s, j)
        inflight = nxt
    return buf


def _exec_stacked(op: StackedRecv, buf, orig, chunks: int, rank, axis: str):
    """Stacked-receive peephole: issue every relay='original' permute,
    stack the arrivals, and write them back with ONE chunk scatter
    instead of a chain of full-buffer dynamic-update-slices."""
    csize = buf.shape[0] // chunks
    arrivals, idxs = [], []
    for (load, send, recv) in op.bodies:
        payload = _select(orig, chunks, load.sel, rank, load.step)
        ax, perm = _send_axis(send, axis)
        arrivals.append(lax.ppermute(payload, ax, perm))
        idxs.append(jnp.asarray(recv.sel.fn(rank, recv.step), jnp.int32))
    stacked = jnp.stack(arrivals, axis=0)
    pos = jnp.stack(idxs)
    grp = buf.reshape((chunks, csize) + buf.shape[1:])
    grp = grp.at[pos].set(stacked.astype(buf.dtype))
    return grp.reshape(buf.shape)


def execute_program(prog: Program, buf, axis, *,
                    use_pallas: bool = False):
    """Execute a compiled micro-op Program on the local shard `buf` inside
    shard_map. `buf` leading dim must be divisible by prog.chunks; returns
    the final buffer (meaning depends on the schedule's `result`).

    `axis` is the mesh axis name for flat programs, or a dict
    {"inter": outer_axis, "intra": inner_axis} for two-level hierarchical
    programs: the flat rank is then composed inner-major
    (intra_index * pod_size + pod_index, matching the schedule's rank
    map) and every SEND ppermutes its level-local perm on its level's
    own mesh axis.

    This is the single data plane: every collective the engine issues —
    whatever the algorithm, codec, or segment count — runs through here.
    """
    if buf.shape[0] % prog.chunks:
        raise ValueError(
            f"buffer leading dim {buf.shape[0]} not divisible by "
            f"{prog.chunks} chunks")
    if isinstance(axis, dict):
        sizes = dict(prog.level_sizes or ())
        if "inter" not in sizes:
            raise ValueError(
                "two-axis execution needs a hierarchical program "
                "(prog.level_sizes is unset)")
        rank = (lax.axis_index(axis["intra"]) * sizes["inter"]
                + lax.axis_index(axis["inter"]))
    else:
        rank = lax.axis_index(axis)
    ops = prog.ops
    i = 0
    if ops and isinstance(ops[0], Copy) and ops[0].kind == "bruck_pre":
        with jax.named_scope("uop.rotate"):
            buf = _chunk_roll(buf, prog.chunks, -rank)
        i = 1
    orig = buf
    prev = buf  # relay='received': step 0 forwards the original input

    # each micro-op runs under the device scope `uop.<kind>`; inside it
    # codec and combine work carry `uop.codec` / `uop.combine`
    while i < len(ops):
        op = ops[i]
        if isinstance(op, Loop):
            with jax.named_scope("uop.loop"):
                buf, prev = _exec_loop(op, buf, orig, prev, prog.chunks,
                                       rank, axis, use_pallas)
            i += 1
        elif isinstance(op, Stream):
            with jax.named_scope("uop.stream"):
                buf, prev = _exec_stream(op, buf, orig, prev, prog.chunks,
                                         prog.nranks, rank, axis,
                                         use_pallas)
            i += 1
        elif isinstance(op, StreamChain):
            with jax.named_scope("uop.chain"):
                buf = _exec_chain(op, buf, orig, prev, prog.chunks,
                                  prog.nranks, rank, axis, use_pallas)
            i += 1
        elif isinstance(op, StackedRecv):
            with jax.named_scope("uop.stacked"):
                buf = _exec_stacked(op, buf, orig, prog.chunks, rank, axis)
            i += 1
        elif isinstance(op, Copy) and op.kind == "bruck_post":
            with jax.named_scope("uop.rotate"):
                buf = _chunk_roll(buf, prog.chunks, rank + 1, reverse=True)
            i += 1
        elif isinstance(op, SegLoop) or (
                isinstance(op, Copy) and op.kind == "load"):
            if isinstance(op, SegLoop):
                body, k_req = op.body, op.segments
                i += 1
            else:
                j = i
                while not isinstance(ops[j], RecvCombine):
                    j += 1
                body, k_req = ops[i:j + 1], 1
                i = j + 1
            step = body[0].step
            with jax.named_scope("uop.exchange"):
                off, mask_idxs, new_val, raw = _exchange_update(
                    body, k_req, buf, orig, prev, prog.chunks, rank, step,
                    axis, use_pallas)
                buf = _apply_write(buf, prog.chunks, off, mask_idxs,
                                   new_val)
            if raw is not None:
                prev = raw
        else:
            raise ValueError(f"unexpected micro-op {op}")
    return buf


# --------------------------------------------------------------------------
# Engine
# --------------------------------------------------------------------------

def _bucket_leaves(leaves, cap: int) -> list:
    """dtype-grouped, size-capped buckets over leaf indices — the ONE
    bucketing rule both `tree_allreduce` and `itree_allreduce` apply
    (grad_sync asserts the two paths bitwise-identical, so the rule must
    not fork)."""
    groups: dict = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    buckets: list[list[int]] = []
    for dtype, idxs in groups.items():
        cur, cur_bytes = [], 0
        for i in idxs:
            nbytes = leaves[i].size * dtype.itemsize
            if cur and cur_bytes + nbytes > cap:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nbytes
        if cur:
            buckets.append(cur)
    return buckets


def _fuse_bucket(leaves, idxs):
    return (leaves[idxs[0]].reshape(-1) if len(idxs) == 1
            else jnp.concatenate([leaves[i].reshape(-1) for i in idxs]))


def _scatter_bucket(leaves, idxs, buf, out) -> None:
    off = 0
    for i in idxs:
        leaf = leaves[i]
        out[i] = buf[off:off + leaf.size].reshape(leaf.shape)
        off += leaf.size


@dataclasses.dataclass
class _TreeTicket:
    """Handle for an in-flight `itree_allreduce`: the bucket requests
    sit in the engine's queue until `wait()` drains them and scatters
    the fused buffers back into the tree."""

    treedef: object
    leaves: list
    plan: list                      # [(leaf indices, Request), ...]

    def wait(self):
        out: list = [None] * len(self.leaves)
        for idxs, req in self.plan:
            _scatter_bucket(self.leaves, idxs, req.wait(), out)
        return jax.tree.unflatten(self.treedef, out)


def _flatten_pad(x, mult: int):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % mult
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, x.shape, x.size


def _find_generator(collective: str, algorithm: str):
    gen = GENERATORS.get((collective, algorithm))
    if gen is None:
        gen = plugins.custom_generator(collective, algorithm)
    if gen is None:
        raise KeyError(
            f"no generator for ({collective!r}, {algorithm!r}); "
            f"register one via plugins.register_collective")
    return gen


def _gen_schedule(collective: str, algorithm: str, comm,
                  root: int = 0, op: str = "add") -> Schedule:
    levels = hierarchical.parse_hier_name(algorithm) \
        if isinstance(algorithm, str) else None
    if levels is not None:
        if not isinstance(comm, ProductComm):
            raise ValueError(
                f"{algorithm!r} needs a two-axis (ProductComm) "
                f"communicator, got {comm!r}")
        intra, inter = levels
        return hierarchical.hierarchical_schedule(
            collective, comm, intra=intra, inter=inter, root=root, op=op)
    if isinstance(comm, ProductComm):
        # a flat algorithm requested over the product group: generate over
        # the equivalent flat communicator — the engine executes it
        # sequentially per axis (level_sizes stays None)
        comm = comm.flat
    gen = _find_generator(collective, algorithm)
    params = inspect.signature(gen).parameters
    kw = {}
    if "root" in params:
        kw["root"] = root
    if "op" in params:
        kw["op"] = op
    return gen(comm, **kw)


def _engine_metrics() -> telemetry.MetricsRegistry:
    reg = telemetry.MetricsRegistry()
    reg.counter("gen_calls")
    reg.counter("sched_cache_hits")
    # auto picks priced under serialized reducing waves, and auto picks
    # that came out segmented (k >= 2)
    reg.counter("selector.serial_wave_choices")
    reg.counter("selector.streamed_choices")
    return reg


@dataclasses.dataclass
class CollectiveEngine:
    """ACCL+ CCLO analogue over a jax mesh.

    backend: 'microcode' (our schedules — the CCLO) or 'native' (XLA
    built-ins — the software-MPI baseline role).
    """

    mesh: jax.sharding.Mesh
    backend: str = "microcode"
    # None: the HwSpec of the mesh's chips (`hw_for_devices`)
    hw: Optional[HwSpec] = None
    selector: Selector = dataclasses.field(default_factory=Selector)
    use_pallas: bool = False
    # static-verifier level applied to every program this engine compiles
    # ("off" | "structural" | "full"; None = REPRO_VERIFY env default) —
    # see core/verify.py
    verify: Optional[str] = None
    # trace-time log of issued collectives (for tests / EXPERIMENTS tables)
    trace_log: list = dataclasses.field(default_factory=list)
    # trace-time schedule cache: (collective, algorithm, n, root, op) ->
    # Schedule. Repeated collectives in a training step hit this instead of
    # re-running the generator (the uC caches compiled microcode).
    _sched_cache: dict = dataclasses.field(default_factory=dict)
    # control-plane telemetry, asserted on by tests (`stats` below is
    # the read-compatible mapping view over this registry)
    metrics: telemetry.MetricsRegistry = dataclasses.field(
        default_factory=_engine_metrics)
    # lazily created request queue (core/sequencer.py) — the CCLO's
    # offload command queue behind the non-blocking `issue` API
    _queue: object = dataclasses.field(default=None, repr=False)

    def __post_init__(self):
        if self.hw is None:
            self.hw = hw_for_devices(self.mesh.devices.flat)

    # -- infrastructure ------------------------------------------------------
    def comm(self, axis):
        """Communicator for one mesh axis, or a `ProductComm` for a
        two-axis tuple (outer pod-crossing axis first)."""
        if isinstance(axis, tuple):
            outer_ax, inner_ax = axis
            return product_comm(self.mesh, outer_ax, inner_ax, self.hw)
        return axis_comm(self.mesh, axis, self.hw)

    def _axis_size(self, axis) -> int:
        if isinstance(axis, tuple):
            n = 1
            for a in axis:
                n *= self.mesh.shape[a]
            return n
        return self.mesh.shape[axis]

    def _product_rank(self, axis: tuple):
        """Flat inner-major rank inside shard_map: intra * P + pod."""
        outer_ax, inner_ax = axis
        return (lax.axis_index(inner_ax) * self.mesh.shape[outer_ax]
                + lax.axis_index(outer_ax))

    @property
    def queue(self):
        """The engine's `Sequencer` (created on first use)."""
        if self._queue is None:
            from repro.core.sequencer import Sequencer
            self._queue = Sequencer(self)
        return self._queue

    @property
    def stats(self) -> telemetry.StatsView:
        """Read-compatible mapping view over `metrics` (legacy name)."""
        return self.metrics.view()

    def _cached_schedule(self, collective: str, algorithm: str,
                         comm, root: int, op: str) -> Schedule:
        # a product communicator keys on its level split, not just the
        # flat rank count — a 4x4 product and a flat 16 must not collide
        shape = ((comm.outer.size, comm.inner.size)
                 if isinstance(comm, ProductComm) else comm.size)
        key = (collective, algorithm, shape, root, op)
        sched = self._sched_cache.get(key)
        if sched is not None:
            self.metrics.inc("sched_cache_hits")
            return sched
        self.metrics.inc("gen_calls")
        with telemetry.current().span("schedule", track="engine",
                                      collective=collective,
                                      algorithm=algorithm):
            sched = _gen_schedule(collective, algorithm, comm, root, op)
        self._sched_cache[key] = sched
        return sched

    def _resolve(self, collective: str, x, axis: str, algorithm: str,
                 root: int = 0, op: str = "add",
                 segments: Optional[int] = None,
                 compression: Optional[str] = None) -> Schedule:
        """Pick algorithm + segment count; return the (cached) schedule.

        The returned schedule carries the chosen segment count in
        `.segments` (caller-supplied `segments` overrides the selector).
        `compression` feeds the selector's compressed-wire pricing: the
        beta term shrinks by the codec's wire ratio and the segment sweep
        prices compressed-segmented variants.
        """
        comm = self.comm(axis)
        if algorithm in (None, "auto"):
            # alltoall executes on the caller's 2-D leading-dim grid, so
            # the selector clamps candidate segments on rows, not the
            # flat element count (priced k == executed k)
            lead = int(x.shape[0]) if collective == "alltoall" \
                and getattr(x, "ndim", 0) else None
            choice = self.selector.choose(
                collective, x.size * x.dtype.itemsize, comm,
                codec=compression, elem_bytes=x.dtype.itemsize,
                lead_dim=lead)
            algorithm = choice.algorithm
            if not comm.hw.reduce_waves_overlap:
                self.metrics.inc("selector.serial_wave_choices")
            if choice.segments >= 2:
                self.metrics.inc("selector.streamed_choices")
            if segments is None:
                segments = choice.segments
            if root == 0 and op == "add":
                # the auto pick already generated exactly this schedule —
                # don't run the generator a second time
                sched = choice.schedule
            else:
                sched = self._cached_schedule(collective, algorithm, comm,
                                              root, op)
        else:
            sched = self._cached_schedule(collective, algorithm, comm,
                                          root, op)
        sched = sched.with_segments(segments if segments else 1)
        self.trace_log.append((collective, algorithm, axis,
                               int(x.size * x.dtype.itemsize)))
        return sched

    def _execute(self, sched: Schedule, buf, axis,
                 compression: Optional[str] = None):
        """Compile (memoized) and run through the one data plane, under
        the device scope `algo.<schedule name>`."""
        prog = sched.compile(codec=compression, verify=self.verify)
        if isinstance(axis, tuple):
            outer_ax, inner_ax = axis
            axis = {"inter": outer_ax, "intra": inner_ax}
        with jax.named_scope("algo." + sched.name):
            return execute_program(prog, buf, axis,
                                   use_pallas=self.use_pallas)

    def run(self, fn, in_specs, out_specs):
        """shard_map wrapper for standalone (F2F-style) engine programs."""
        return jax.jit(jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))

    # -- two-axis (hierarchical) dispatch ------------------------------------
    def _sequential_product(self, collective: str, x, axis: tuple, *,
                            op: str = "add", root: int = 0,
                            compression: Optional[str] = None):
        """Per-axis composition over (outer, inner): the fallback the
        engine executes when a FLAT algorithm wins the product pricing
        (or the backend is native) — one single-axis collective per
        level, each re-resolved on its own fabric."""
        outer_ax, inner_ax = axis
        P = self.mesh.shape[outer_ax]
        if collective == "allreduce":
            M = self.mesh.shape[inner_ax]
            flat, shape, size = _flatten_pad(x, M)
            shard = self.reduce_scatter(flat, inner_ax, op=op,
                                        compression=compression)
            shard = self.allreduce(shard, outer_ax, op=op,
                                   compression=compression)
            full = self.allgather(shard, inner_ax)
            return full[:size].reshape(shape)
        if collective == "reduce_scatter":
            # inner-major rank map: slice r of (RS inner -> RS outer) is
            # exactly flat slice r = intra * P + pod
            shard = self.reduce_scatter(x, inner_ax, op=op,
                                        compression=compression)
            return self.reduce_scatter(shard, outer_ax, op=op,
                                       compression=compression)
        if collective == "allgather":
            part = self.allgather(x, outer_ax)
            return self.allgather(part, inner_ax)
        if collective == "bcast":
            # inner first: after it every member of the root's pod
            # (pod index root % P) holds the data; the outer bcast then
            # fans each intra slot's copy across pods
            y = self.bcast(x, inner_ax, root=root // P)
            return self.bcast(y, outer_ax, root=root % P)
        raise ValueError(f"no two-axis composition for {collective!r}")

    def _product_collective(self, collective: str, x, axis: tuple, *,
                            op: str = "add", root: int = 0,
                            algorithm: str = "auto",
                            compression: Optional[str] = None,
                            segments: Optional[int] = None):
        """Collective over a two-axis (outer, inner) product group.

        Resolves against the `ProductComm`: a hierarchical pick executes
        as ONE two-level program (intra steps ppermute on the inner mesh
        axis, inter steps on the outer one — DCN carries 1/ici_size of
        the bytes); a flat pick executes as the sequential per-axis
        composition it was priced against. A size-1 level degenerates to
        the ordinary single-axis path.
        """
        outer_ax, inner_ax = axis

        def single(ax):
            if collective == "allreduce":
                return self.allreduce(x, ax, op=op, algorithm=algorithm,
                                      compression=compression,
                                      segments=segments)
            if collective == "reduce_scatter":
                return self.reduce_scatter(x, ax, op=op,
                                           algorithm=algorithm,
                                           compression=compression,
                                           segments=segments)
            if collective == "allgather":
                return self.allgather(x, ax, algorithm=algorithm,
                                      segments=segments)
            return self.bcast(x, ax, root=root, algorithm=algorithm,
                              segments=segments)

        if self.mesh.shape[outer_ax] == 1:
            return single(inner_ax)
        if self.mesh.shape[inner_ax] == 1:
            return single(outer_ax)
        if self.backend == "native" and algorithm in (None, "auto"):
            return self._sequential_product(collective, x, axis, op=op,
                                            root=root,
                                            compression=compression)
        if collective == "bcast" and root != 0:
            # the two-level bcast composition is root=0 only (see
            # hierarchical.hier_bcast); other roots run per axis
            return self._sequential_product("bcast", x, axis, root=root)
        sched = self._resolve(collective, x, axis, algorithm, root=root,
                              op=op, segments=segments,
                              compression=compression)
        if sched.level_sizes is None:
            return self._sequential_product(collective, x, axis, op=op,
                                            root=root,
                                            compression=compression)
        if collective == "reduce_scatter":
            if x.size % sched.chunks:
                raise ValueError(
                    f"reduce_scatter size {x.size} % {sched.chunks} != 0")
            flat = x.reshape(-1)
            out = self._execute(sched, flat, axis, compression)
            rank = self._product_rank(axis)
            csize = flat.shape[0] // sched.chunks
            own = sched.owned_chunk(rank)
            return lax.dynamic_slice_in_dim(out, own * csize, csize, 0)
        if collective == "allgather":
            n = self._axis_size(axis)
            flat = x.reshape(-1)
            rank = self._product_rank(axis)
            buf = jnp.zeros((n * flat.shape[0],), flat.dtype)
            buf = lax.dynamic_update_slice_in_dim(
                buf, flat, rank * flat.shape[0], 0)
            return self._execute(sched, buf, axis)
        # allreduce / bcast: full result, chunk-padded like the flat path
        flat, shape, size = _flatten_pad(x, sched.chunks)
        out = self._execute(sched, flat, axis, compression)
        return out[:size].reshape(shape)

    # -- MPI-like API (paper Listing 1) --------------------------------------
    @telemetry.named_scope("engine.allreduce")
    def allreduce(self, x, axis, op: str = "add",
                  algorithm: str = "auto",
                  compression: Optional[str] = None,
                  segments: Optional[int] = None):
        if isinstance(axis, tuple):
            return self._product_collective(
                "allreduce", x, axis, op=op, algorithm=algorithm,
                compression=compression, segments=segments)
        n = self.mesh.shape[axis]
        if n == 1:
            return x
        if self.backend == "native" and algorithm in (None, "auto"):
            with jax.named_scope("algo.native"):
                if op == "add":
                    return lax.psum(x, axis)
                if op == "max":
                    return lax.pmax(x, axis)
                if op == "min":
                    return lax.pmin(x, axis)
        sched = self._resolve("allreduce", x, axis, algorithm, op=op,
                              segments=segments, compression=compression)
        # Padding stays a function of chunks alone so the chunk layout —
        # and hence the elementwise reduction order — is identical at
        # every segment count (uncompressed segmented lowerings are
        # bitwise-equal to unsegmented ones; compressed ones too, by the
        # scale-block alignment clamp in the executor).
        flat, shape, size = _flatten_pad(x, sched.chunks)
        out = self._execute(sched, flat, axis, compression)
        return out[:size].reshape(shape)

    @telemetry.named_scope("engine.reduce_scatter")
    def reduce_scatter(self, x, axis, op: str = "add",
                       algorithm: str = "auto",
                       compression: Optional[str] = None,
                       segments: Optional[int] = None):
        """Tiled semantics on the flattened array: rank r gets slice r of
        the reduction. Input size must be divisible by the rank count."""
        if isinstance(axis, tuple):
            return self._product_collective(
                "reduce_scatter", x, axis, op=op, algorithm=algorithm,
                compression=compression, segments=segments)
        n = self.mesh.shape[axis]
        if n == 1:
            return x
        if x.size % n:
            raise ValueError(f"reduce_scatter size {x.size} % {n} != 0")
        if self.backend == "native" and algorithm in (None, "auto"):
            with jax.named_scope("algo.native"):
                return lax.psum_scatter(x.reshape(n, -1), axis,
                                        scatter_dimension=0,
                                        tiled=False).reshape(-1)
        sched = self._resolve("reduce_scatter", x, axis, algorithm, op=op,
                              segments=segments, compression=compression)
        flat = x.reshape(-1)
        out = self._execute(sched, flat, axis, compression)
        rank = lax.axis_index(axis)
        csize = flat.shape[0] // n
        own = sched.owned_chunk(rank)
        return lax.dynamic_slice_in_dim(out, own * csize, csize, 0)

    @telemetry.named_scope("engine.allgather")
    def allgather(self, x, axis, algorithm: str = "auto",
                  segments: Optional[int] = None):
        """Tiled: returns concat of every rank's flat x (own shard at
        position rank)."""
        if isinstance(axis, tuple):
            return self._product_collective(
                "allgather", x, axis, algorithm=algorithm,
                segments=segments)
        n = self.mesh.shape[axis]
        if n == 1:
            return x.reshape(-1)
        if self.backend == "native" and algorithm in (None, "auto"):
            with jax.named_scope("algo.native"):
                return lax.all_gather(x.reshape(-1), axis, axis=0,
                                      tiled=True)
        sched = self._resolve("allgather", x, axis, algorithm,
                              segments=segments)
        flat = x.reshape(-1)
        rank = lax.axis_index(axis)
        buf = jnp.zeros((n * flat.shape[0],), flat.dtype)
        buf = lax.dynamic_update_slice_in_dim(
            buf, flat, rank * flat.shape[0], 0)
        return self._execute(sched, buf, axis)

    @telemetry.named_scope("engine.bcast")
    def bcast(self, x, axis, root: int = 0, algorithm: str = "auto",
              segments: Optional[int] = None):
        if isinstance(axis, tuple):
            return self._product_collective(
                "bcast", x, axis, root=root, algorithm=algorithm,
                segments=segments)
        n = self.mesh.shape[axis]
        if n == 1:
            return x
        if self.backend == "native" and algorithm in (None, "auto"):
            with jax.named_scope("algo.native"):
                return lax.all_gather(x, axis)[root]
        sched = self._resolve("bcast", x, axis, algorithm, root=root,
                              segments=segments)
        flat, shape, size = _flatten_pad(x, sched.chunks)
        out = self._execute(sched, flat, axis)
        return out[:size].reshape(shape)

    @telemetry.named_scope("engine.reduce")
    def reduce(self, x, axis: str, root: int = 0, op: str = "add",
               algorithm: str = "auto", segments: Optional[int] = None):
        """MPI semantics: result meaningful at `root` only (other ranks may
        hold partial reductions, depending on the algorithm)."""
        n = self.mesh.shape[axis]
        if n == 1:
            return x
        if self.backend == "native" and algorithm in (None, "auto"):
            with jax.named_scope("algo.native"):
                return lax.psum(x, axis)
        sched = self._resolve("reduce", x, axis, algorithm, root=root,
                              op=op, segments=segments)
        flat, shape, size = _flatten_pad(x, sched.chunks)
        out = self._execute(sched, flat, axis)
        return out[:size].reshape(shape)

    @telemetry.named_scope("engine.gather")
    def gather(self, x, axis: str, root: int = 0, algorithm: str = "auto"):
        """Root ends with concat of all ranks' flat x (others undefined)."""
        n = self.mesh.shape[axis]
        if n == 1:
            return x.reshape(-1)
        if self.backend == "native" and algorithm in (None, "auto"):
            with jax.named_scope("algo.native"):
                return lax.all_gather(x.reshape(-1), axis, axis=0,
                                      tiled=True)
        sched = self._resolve("gather", x, axis, algorithm, root=root)
        flat = x.reshape(-1)
        rank = lax.axis_index(axis)
        buf = jnp.zeros((n * flat.shape[0],), flat.dtype)
        own_slot = rank if sched.chunk_coords == "absolute" else (rank - root) % n
        buf = lax.dynamic_update_slice_in_dim(
            buf, flat, own_slot * flat.shape[0], 0)
        out = self._execute(sched, buf, axis)
        if sched.chunk_coords == "relative":
            grp = out.reshape((n, flat.shape[0]))
            out = jnp.roll(grp, root, axis=0).reshape(-1)
        return out

    @telemetry.named_scope("engine.alltoall")
    def alltoall(self, x, axis: str, algorithm: str = "auto",
                 segments: Optional[int] = None):
        """Tiled on leading dim: block j of the output came from rank j."""
        n = self.mesh.shape[axis]
        if n == 1:
            return x
        if x.shape[0] % n:
            raise ValueError(f"alltoall dim0 {x.shape[0]} % {n} != 0")
        if self.backend == "native" and algorithm in (None, "auto"):
            with jax.named_scope("algo.native"):
                return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                      tiled=True)
        sched = self._resolve("alltoall", x, axis, algorithm,
                              segments=segments)
        return self._execute(sched, x, axis)

    @telemetry.named_scope("engine.collective")
    def collective(self, name: str, x, axis: str, *,
                   algorithm: str = "auto", root: int = 0, op: str = "add",
                   compression: Optional[str] = None,
                   segments: Optional[int] = None):
        """Run a collective registered via `plugins.register_collective`.

        The paper's "new collectives without re-synthesis" path: an
        out-of-tree schedule generator lowers through the same selector,
        compiler, and `execute_program` data plane as the built-ins (see
        examples/custom_collective.py). Result convention follows the
        schedule: 'shard' returns this rank's owned chunk, anything else
        the full (trimmed) buffer.
        """
        n = self.mesh.shape[axis]
        if n == 1:
            return x
        sched = self._resolve(name, x, axis, algorithm, root=root, op=op,
                              segments=segments, compression=compression)
        if sched.result == "shard" and x.size % sched.chunks:
            # a shard result returns one raw chunk — padding would hand
            # some rank silent zeros (reduce_scatter applies the same rule)
            raise ValueError(
                f"{name} returns shards: input size {x.size} must be "
                f"divisible by {sched.chunks} chunks")
        flat, shape, size = _flatten_pad(x, sched.chunks)
        out = self._execute(sched, flat, axis, compression)
        if sched.result == "shard":
            rank = lax.axis_index(axis)
            csize = flat.shape[0] // sched.chunks
            own = sched.owned_chunk(rank)
            return lax.dynamic_slice_in_dim(out, own * csize, csize, 0)
        return out[:size].reshape(shape)

    @telemetry.named_scope("engine.send_recv", "algo.ring")
    def send_recv(self, x, axis: str, shift: int = 1):
        """Neighbour exchange along a ring (the paper's send/recv pair)."""
        comm = self.comm(axis)
        return lax.ppermute(x, axis, comm.ring_perm(shift))

    @telemetry.named_scope("engine.barrier")
    def barrier(self, axis: str):
        """1-element allreduce, like the paper's barrier collective."""
        return self.allreduce(jnp.zeros((1,), jnp.float32), axis,
                              algorithm="auto")

    def nop(self):
        """Engine invocation NOP (fig8 latency benchmark)."""
        return jnp.zeros((), jnp.int32)

    # -- non-blocking request API (the collective offload queue) -------------
    #
    # SIGNATURE CONTRACT: `CollectiveEngine.issue` / `issue_multi` are
    # thin delegates of `Sequencer.issue` / `Sequencer.issue_multi` and
    # accept the identical public call shapes — same parameter order,
    # same `after=None` / `timeout=None` keyword-only defaults (the
    # sequencer's `_pre`/`_post`/`_shape` hooks are private plumbing the
    # engine surface does not expose). The `i*` helpers fix the
    # collective name and otherwise take `issue`'s keywords. Asserted by
    # `tests/test_api_surface.py`.
    def issue(self, collective: str, x, axis: str, *, after=None,
              timeout: Optional[float] = None, **kwargs):
        """Enqueue a collective without executing it; returns a `Request`
        handle immediately (the CCLO request-queue contract — paper use
        case 1). `x` may be an array or another `Request` (a dependency
        edge: this call consumes that request's result). Materialize
        with `Request.wait()` or `engine.queue.drain()`; the queue keeps
        per-communicator FIFO order, infers conflict edges from buffer
        identity (override with `after=`), enforces `timeout` (virtual
        seconds) on the simulated drain's clock, and coalesces
        consecutive small same-(op, dtype) reductions into one bucketed
        program — see `core/sequencer.py`. Remaining keywords are those
        of the blocking method (`op`, `root`, `algorithm`,
        `compression`, `segments`).
        """
        return self.queue.issue(collective, x, axis, after=after,
                                timeout=timeout, **kwargs)

    def issue_multi(self, x, axes, op: str = "add",
                    algorithm: str = "auto",
                    compression: Optional[str] = None):
        """Non-blocking `allreduce_multi`: the hierarchical multi-axis
        allreduce as queued work (`Sequencer.issue_multi` — two live
        axes fold into one tuple-axis request; more chain RS ->
        recurse -> AG with dependency edges)."""
        return self.queue.issue_multi(x, axes, op=op, algorithm=algorithm,
                                      compression=compression)

    def iallreduce(self, x, axis: str, *, after=None,
                   timeout: Optional[float] = None, **kwargs):
        """Non-blocking `allreduce` (MPI_Iallreduce analogue)."""
        return self.issue("allreduce", x, axis, after=after,
                          timeout=timeout, **kwargs)

    def ireduce_scatter(self, x, axis: str, *, after=None,
                        timeout: Optional[float] = None, **kwargs):
        """Non-blocking `reduce_scatter`."""
        return self.issue("reduce_scatter", x, axis, after=after,
                          timeout=timeout, **kwargs)

    def iallgather(self, x, axis: str, *, after=None,
                   timeout: Optional[float] = None, **kwargs):
        """Non-blocking `allgather`."""
        return self.issue("allgather", x, axis, after=after,
                          timeout=timeout, **kwargs)

    def ibcast(self, x, axis: str, *, after=None,
               timeout: Optional[float] = None, **kwargs):
        """Non-blocking `bcast`."""
        return self.issue("bcast", x, axis, after=after,
                          timeout=timeout, **kwargs)

    def ireduce(self, x, axis: str, *, after=None,
                timeout: Optional[float] = None, **kwargs):
        """Non-blocking `reduce`."""
        return self.issue("reduce", x, axis, after=after,
                          timeout=timeout, **kwargs)

    def ialltoall(self, x, axis: str, *, after=None,
                  timeout: Optional[float] = None, **kwargs):
        """Non-blocking `alltoall`."""
        return self.issue("alltoall", x, axis, after=after,
                          timeout=timeout, **kwargs)

    def icollective(self, name: str, x, axis: str, *, after=None,
                    timeout: Optional[float] = None, **kwargs):
        """Non-blocking plugin-registered collective (`collective`)."""
        return self.issue(name, x, axis, after=after,
                          timeout=timeout, **kwargs)

    # -- hierarchical multi-axis collectives (multi-pod path) ----------------
    @telemetry.named_scope("engine.allreduce_multi")
    def allreduce_multi(self, x, axes: Sequence[str], op: str = "add",
                        algorithm: str = "auto",
                        compression: Optional[str] = None):
        """Hierarchical allreduce over several axes, fastest axis first.

        RS over axes[0] -> recurse over the rest on 1/n of the bytes -> AG
        back over axes[0]. Across pods this sends only 1/|data| of the
        gradient bytes over DCN — the multi-pod collective optimization.
        (The pod axis prices its own segment floor: see
        `HwSpec.dcn_min_segment_bytes`.)
        """
        axes = [a for a in axes if self.mesh.shape[a] > 1]
        if not axes:
            return x
        if len(axes) == 1:
            return self.allreduce(x, axes[0], op=op, algorithm=algorithm,
                                  compression=compression)
        if len(axes) == 2:
            # two-level case: ONE hierarchical program over the
            # (outer x inner) product replaces the RS/recurse/AG
            # sandwich (axes are ordered fastest first, so the slow
            # pod-crossing axis is the last one)
            return self.allreduce(x, (axes[1], axes[0]), op=op,
                                  algorithm=algorithm,
                                  compression=compression)
        n0 = self.mesh.shape[axes[0]]
        flat, shape, size = _flatten_pad(x, n0)
        shard = self.reduce_scatter(flat, axes[0], op=op,
                                    algorithm=algorithm,
                                    compression=compression)
        shard = self.allreduce_multi(shard, axes[1:], op=op,
                                     algorithm=algorithm,
                                     compression=compression)
        full = self.allgather(shard, axes[0], algorithm=algorithm)
        return full[:size].reshape(shape)

    # -- streaming API (paper Listing 2): compute fused with communication ---
    def _matmul(self, a, b, out_dtype=None):
        out_dtype = out_dtype or a.dtype
        if self.use_pallas:
            from repro.kernels import ops as kops
            return kops.matmul(a, b).astype(out_dtype)
        return jnp.dot(a, b,
                       preferred_element_type=jnp.float32).astype(out_dtype)

    @telemetry.named_scope("engine.allgather_matmul", "algo.ring")
    def allgather_matmul(self, x, w, axis: str, segments: int = 1):
        """y = allgather(x, rows) @ w without staging the gathered buffer.

        Each ring step multiplies the resident shard while the next shard is
        on the wire — the streaming collective of Listing 2, fused with the
        MXU consumer. x: (m, k) local rows; w: (k, p); out: (n*m, p).

        With segments > 1 the shard is row-split into independent segment
        pipelines: segment j's matmul at step s+1 depends only on segment
        j's ppermute at step s, so a late segment never stalls the MXU on
        the rest of the shard.
        """
        n = self.mesh.shape[axis]
        if n == 1:
            return self._matmul(x, w)
        comm = self.comm(axis)
        rank = lax.axis_index(axis)
        m = x.shape[0]
        segs = _fit_segments(m, segments)
        out = jnp.zeros((n * m, w.shape[-1]), x.dtype)
        # resident shard kept as per-segment arrays — never concatenated,
        # so each segment's wire/compute chain stays independent
        parts = list(jnp.split(x, segs, axis=0)) if segs > 1 else [x]
        sub = m // segs
        for s in range(n):
            for j, part in enumerate(parts):
                seg_out = self._matmul(part, w)
                out = lax.dynamic_update_slice_in_dim(
                    out, seg_out, ((rank - s) % n) * m + j * sub, 0)
            if s < n - 1:
                parts = [lax.ppermute(p, axis, comm.ring_perm(1))
                         for p in parts]
        self.trace_log.append(("allgather_matmul", "ring", axis,
                               int(x.size * x.dtype.itemsize)))
        return out

    @telemetry.named_scope("engine.matmul_reduce_scatter", "algo.ring")
    def matmul_reduce_scatter(self, x, w, axis: str, segments: int = 1):
        """Row-sharded output of (x @ w) with the partial-sum reduction
        streamed around the ring. x: (m, k_local); w: (k_local, p);
        out: (m/n, p) — rank r holds row-chunk r, fully summed.

        segments > 1 splits the rotating accumulator into independent
        row-segment pipelines (wire of segment j overlaps the adds of the
        other segments)."""
        n = self.mesh.shape[axis]
        partial = self._matmul(x, w)
        if n == 1:
            return partial
        comm = self.comm(axis)
        rank = lax.axis_index(axis)
        m = partial.shape[0]
        if m % n:
            raise ValueError(f"matmul_reduce_scatter rows {m} % {n} != 0")
        c = m // n
        segs = _fit_segments(c, segments)
        sub = c // segs
        accs = [lax.dynamic_slice_in_dim(
            partial, ((rank - 1) % n) * c + j * sub, sub, 0)
            for j in range(segs)]
        for s in range(1, n):
            accs = [lax.ppermute(a, axis, comm.ring_perm(1)) for a in accs]
            accs = [a + lax.dynamic_slice_in_dim(
                partial, ((rank - 1 - s) % n) * c + j * sub, sub, 0)
                for j, a in enumerate(accs)]
        self.trace_log.append(("matmul_reduce_scatter", "ring", axis,
                               int(partial.size * partial.dtype.itemsize)))
        return accs[0] if segs == 1 else jnp.concatenate(accs, axis=0)

    @telemetry.named_scope("engine.ring_attention", "algo.ring")
    def ring_attention(self, q, k, v, axis: str, *, causal: bool = True,
                       scale: Optional[float] = None, segments: int = 1):
        """Context-parallel attention: the streaming API generalized.

        q, k, v: (B, S_local, H, hd) — the SEQUENCE is sharded over `axis`
        (heads replicated across it). KV blocks rotate around the ring
        while each rank flash-accumulates attention for its local queries:
        data streams through compute without ever materializing the
        gathered sequence (paper Listing 2, applied to attention).

        Inference/prefill form (no custom VJP). Returns (B, S_local, H, hd).
        """
        n = self.mesh.shape[axis]
        b, sl, h, hd = q.shape
        if scale is None:
            scale = 1.0 / (hd ** 0.5)
        if n == 1:
            kv = k.shape[2]
            qr = q.reshape(b, sl, kv, h // kv, hd)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qr, k,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                mask = jnp.tril(jnp.ones((sl, sl), bool))
                s = jnp.where(mask[None, None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(v.dtype), v)
            return out.transpose(0, 3, 1, 2, 4).reshape(b, sl, h, hd)

        comm = self.comm(axis)
        rank = lax.axis_index(axis)
        kv = k.shape[2]
        g = h // kv
        qr = q.reshape(b, sl, kv, g, hd)
        q_pos = rank * sl + jnp.arange(sl)

        m0 = jnp.full((b, kv, g, sl), -1e30, jnp.float32)
        l0 = jnp.zeros((b, kv, g, sl), jnp.float32)
        a0 = jnp.zeros((b, kv, g, sl, hd), jnp.float32)

        def accumulate(carry, kv_blk, owner, seg_off=0):
            m, l, acc = carry
            kb, vb = kv_blk
            k_pos = owner * sl + seg_off + jnp.arange(kb.shape[1])
            s = jnp.einsum("bqkgh,bskh->bkgqs", qr, kb,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                mask = k_pos[None, :] <= q_pos[:, None]
                s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            return m_new, l, acc * corr[..., None] + pv

        # KV blocks rotate as independent sequence segments: segment j's
        # flash-accumulate at step s+1 depends only on segment j's
        # ppermute at step s (online softmax is exact under any block
        # split, so segmentation leaves the math unchanged).
        segs = _fit_segments(sl, segments)
        sub = sl // segs
        k_parts = list(jnp.split(k, segs, axis=1)) if segs > 1 else [k]
        v_parts = list(jnp.split(v, segs, axis=1)) if segs > 1 else [v]

        carry = (m0, l0, a0)
        for j in range(segs):
            carry = accumulate(carry, (k_parts[j], v_parts[j]), rank,
                               seg_off=j * sub)
        for step in range(1, n):
            # next block rides the wire while the current one computes
            k_parts = [lax.ppermute(p, axis, comm.ring_perm(1))
                       for p in k_parts]
            v_parts = [lax.ppermute(p, axis, comm.ring_perm(1))
                       for p in v_parts]
            owner = (rank - step) % n
            for j in range(segs):
                carry = accumulate(carry, (k_parts[j], v_parts[j]), owner,
                                   seg_off=j * sub)
        m, l, acc = carry
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        self.trace_log.append(("ring_attention", "ring", axis,
                               int(k.size * k.dtype.itemsize)))
        return out.transpose(0, 3, 1, 2, 4).reshape(b, sl, h, hd)

    # -- gradient-bucket collectives (offload-engine H2H role) ---------------
    #: default gradient-bucket cap; sized so a bucket fills the segmented
    #: ring pipeline without monopolizing HBM for the fused buffer.
    BUCKET_BYTES = 4 << 20

    @telemetry.named_scope("engine.tree_allreduce")
    def tree_allreduce(self, tree, axes: Sequence[str], op: str = "add",
                       compression: Optional[str] = None,
                       algorithm: str = "auto",
                       bucket_bytes: Optional[int] = None):
        """Bucketed pytree allreduce: fused collectives over leaf groups.

        Leaves are grouped by dtype (wire bytes stay native — a bf16
        gradient ships 2 bytes/elem, no blanket fp32 upcast) and packed
        into buckets of at most `bucket_bytes` each. Concatenating leaves
        amortizes the alpha term; capping the bucket keeps several
        collectives in flight so buckets pipeline through the segmented
        rings instead of serializing behind one giant fused buffer.
        """
        leaves, treedef = jax.tree.flatten(tree)
        if not leaves:
            return tree
        cap = bucket_bytes if bucket_bytes is not None else self.BUCKET_BYTES
        out: list = [None] * len(leaves)
        for idxs in _bucket_leaves(leaves, cap):
            buf = self.allreduce_multi(_fuse_bucket(leaves, idxs), axes,
                                       op=op, algorithm=algorithm,
                                       compression=compression)
            _scatter_bucket(leaves, idxs, buf, out)
        return jax.tree.unflatten(treedef, out)

    def itree_allreduce(self, tree, axes: Sequence[str], op: str = "add",
                        compression: Optional[str] = None,
                        algorithm: str = "auto",
                        bucket_bytes: Optional[int] = None):
        """Non-blocking `tree_allreduce`: every bucket's hierarchical
        allreduce is ISSUED into the request queue up front and a ticket
        is returned; `ticket.wait()` drains the requests and rebuilds
        the tree. Because a caller can collect several tickets before
        waiting any (the trainer's gradient sync does exactly this), all
        buckets across all calls sit in the queue together — small
        same-dtype buckets coalesce into one program and the makespan
        model prices their drain as one overlapped queue instead of a
        blocking sequence."""
        leaves, treedef = jax.tree.flatten(tree)
        if not leaves:
            return _TreeTicket(treedef=treedef, leaves=[], plan=[])
        cap = bucket_bytes if bucket_bytes is not None else self.BUCKET_BYTES
        plan = []
        for idxs in _bucket_leaves(leaves, cap):
            req = self.queue.issue_multi(_fuse_bucket(leaves, idxs), axes,
                                         op=op, algorithm=algorithm,
                                         compression=compression)
            plan.append((idxs, req))
        return _TreeTicket(treedef=treedef, leaves=leaves, plan=plan)
