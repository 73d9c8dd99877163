"""Device-resident flat client-state store — where client models LIVE.

The async runtime (PR 2) kept per-client model snapshots as a Python
``Dict[int, pytree]``: N full scattered copies of the model, re-stacked
leaf by leaf (``tree_map(jnp.stack)``) on every drained window before
the cohort could train.  The flatten-once ``(N, P)`` representation the
Pallas fedagg kernel already uses for aggregation is the natural home
for that state instead: ``ClientStateStore`` holds every client's
snapshot as one row of a single device-resident ``(N, P)`` f32 buffer
— plus, for models that carry non-float state (step counters, masks),
a sidecar ``(N, Pi)`` int32 segment — with the unflatten spec (per-leaf
segment/offset/shape/dtype views) cached once at construction.

* ``gather(ids)`` returns the stacked start-params pytree for a cohort
  (one device program: row gather + per-leaf slice/reshape/cast) — no
  per-leaf host stacking, no dict lookups.
* ``scatter(ids, flat_global)`` writes one global row into the merged
  clients' slots via ``buf.at[ids].set(...)`` under a jit that DONATES
  the buffers on every backend, so the store updates in place instead
  of copying N*P floats per window.
* ``merge_scatter(ids, stacked_updates, coef, global_flat)`` is the
  fused tail of the async round step: staleness merge (global model as
  the implicit row 0, zero-coefficient rows masked to exact no-ops —
  the straggler-mask convention, which also makes padded rows free) +
  flatten of the new global row + scatter, ONE jitted buffer-donating
  program per padded cohort-size bucket.  ``use_kernel=True``
  dispatches the merge through the folded Pallas fedagg kernel
  (``fedagg_fold_pytree``),
  the SAME program the dict-of-pytrees reference's
  ``staleness_weighted_merge(use_kernel=True)`` runs, so kernel-path
  histories stay bit-identical between the two snapshot paths.

Donation contract: the store owns its buffers.  Callers must NOT hold
references into ``store.buffer``/``store.int_buffer`` across
``scatter``/``merge_scatter`` calls — the old buffer is invalidated
in place.  ``gather``/``gather_one`` return
fresh arrays and are always safe.

Sharding: pass a 1-D client mesh to shard the row axis across devices
(rows padded to a mesh multiple via ``ClientShardingPlan`` — the extra
rows are never addressed).  Gather/merge/scatter then run as GSPMD
programs over the row-sharded buffers, composing with the sharded
engine's cohort padding.

Dtype note (segment layout): f32/bf16/f16 leaves live in the f32 row
segment (every bf16/f16 value is exactly representable in f32 — exact
round-trip).  bool and integer leaves of <= 32 bits live in the int32
sidecar segment: bool/int8/int16/int32/uint8/uint16 values embed
exactly in int32 (plain ``astype`` both ways); uint32 round-trips via
``lax.bitcast_convert_type`` (bit pattern preserved).  Leaves the
store cannot carry exactly — 64-bit ints, f64, complex — are rejected
at construction with ``TypeError``.

Quantized rows (``quant_bits=8``): the float segment is stored as a
shifted-scale int8 buffer plus a tiny per-leaf f32 scale/zero-point
sidecar (``(rows, 2L)`` for L float leaves — the int32 sidecar
machinery generalized to a third segment).  Writes quantize inside
``scatter``/``scatter_params``/``merge_scatter`` and reads dequantize
inside ``gather``/``gather_one`` as jitted programs per cohort bucket
— only cohort-sized ``(K, Pf)`` blocks ever exist in f32, the hot loop
never materializes an f32 ``(N, P)`` buffer.  The quantize and
dequantize math each live in ONE standalone compiled program shared by
every residency layout (the donated row writes are separate programs):
fusing them into buffer-shaped programs would let XLA re-fuse their
float math per compilation unit and break cross-layout bit-identity,
as a fused merge once did.  Server-side **error-feedback accumulators**
(on by default) keep each client's quantization residual ``x - dq(q(x))`` in
sparse host memory — it models state a real deployment keeps at the
client, so it is NOT counted as store bytes — and add it back before
the next quantization of that client's row, making the stored
snapshot unbiased over successive writes.  Contract: ``quant_bits=32``
(the default) is byte-for-byte the existing store path; quantized runs
are seeded-deterministic (dense/tiered/disk layouts stay bit-identical
to EACH OTHER — every quantize runs the same segment-min/max program)
but carry a gated convergence delta vs the f32 reference.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import _merge_folded_jnp
from repro.kernels.ops import (dequantize_rows, dequantize_segment,
                               fedagg_fold_pytree, quantize_rows,
                               tree_spec)
from repro.obs import telemetry as obs

_FLOAT_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


def _leaf_kind(dtype) -> str:
    """Segment + conversion rule of one leaf dtype: "f" (f32 segment),
    "i" (int32 sidecar, value-exact astype), "u32" (int32 sidecar,
    bitcast).  Raises TypeError for dtypes with no exact carrier."""
    d = jnp.dtype(dtype)
    if d in [jnp.dtype(x) for x in _FLOAT_DTYPES]:
        return "f"
    if d == jnp.dtype(jnp.uint32):
        return "u32"
    if (np.issubdtype(d, np.integer) or d == np.dtype(bool)) \
            and d.itemsize <= 4:
        return "i"
    raise TypeError(
        f"ClientStateStore rows are f32 + int32 segments: leaf dtype "
        f"{dtype} does not round-trip exactly (float leaves up to f32 "
        "and bool/int leaves up to 32 bits only)")


def _segment_entries(spec):
    """tree_spec entries -> per-leaf (kind, segment offset, size, shape,
    dtype) with float and sidecar offsets accumulated independently.
    Returns (entries, float width Pf, sidecar width Pi)."""
    entries, f_off, i_off = [], 0, 0
    for _, size, shape, dtype in spec:
        kind = _leaf_kind(dtype)
        if kind == "f":
            entries.append((kind, f_off, size, shape, dtype))
            f_off += size
        else:
            entries.append((kind, i_off, size, shape, dtype))
            i_off += size
    return tuple(entries), f_off, i_off


def _to_rows(tree, entries):
    """Model pytree -> ((Pf,) f32 row, (Pi,) int32 row); either row may
    be zero-width."""
    leaves = jax.tree_util.tree_leaves(tree)
    f_parts, i_parts = [], []
    for l, (kind, _, _, _, _) in zip(leaves, entries):
        x = jnp.asarray(l)
        if kind == "f":
            f_parts.append(x.reshape(-1).astype(jnp.float32))
        elif kind == "i":
            i_parts.append(x.reshape(-1).astype(jnp.int32))
        else:
            i_parts.append(
                jax.lax.bitcast_convert_type(x, jnp.int32).reshape(-1))
    frow = (jnp.concatenate(f_parts) if f_parts
            else jnp.zeros((0,), jnp.float32))
    irow = (jnp.concatenate(i_parts) if i_parts
            else jnp.zeros((0,), jnp.int32))
    return frow, irow


def _leaf_from(seg, off, size, lead, kind, shape, dtype):
    x = seg[..., off:off + size].reshape(lead + shape)
    if kind == "u32":
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    return x.astype(dtype)


def _from_rows(frow, irow, treedef, entries):
    """((Pf,), (Pi,)) rows -> model pytree (exact per-leaf dtypes)."""
    outs = [_leaf_from(frow if kind == "f" else irow, off, size, (),
                       kind, shape, dtype)
            for kind, off, size, shape, dtype in entries]
    return jax.tree_util.tree_unflatten(treedef, outs)


def _from_stacked_rows(frows, irows, treedef, entries):
    """((K, Pf), (K, Pi)) row blocks -> stacked pytree, leaves (K, ...)."""
    k = frows.shape[0]
    outs = [_leaf_from(frows if kind == "f" else irows, off, size, (k,),
                       kind, shape, dtype)
            for kind, off, size, shape, dtype in entries]
    return jax.tree_util.tree_unflatten(treedef, outs)


def _float_segs(entries):
    """Static tuple of (offset, size) float-segment views in row order —
    the per-leaf layout ``quantize_rows``/``dequantize_segment`` slice."""
    return tuple((off, size) for kind, off, size, _, _ in entries
                 if kind == "f")


def _from_quant_rows(qrows, mrows, irows, lead, treedef, entries, fsegs):
    """Quantized row blocks -> pytree.  Float leaves dequantize straight
    into their leaf shapes (``dequantize_segment`` per leaf — no full
    f32 row is ever concatenated); sidecar leaves as in the f32 path."""
    outs, j = [], 0
    for kind, off, size, shape, dtype in entries:
        if kind == "f":
            x = dequantize_segment(qrows, mrows, fsegs, j)
            outs.append(x.reshape(lead + shape).astype(dtype))
            j += 1
        else:
            outs.append(_leaf_from(irows, off, size, lead, kind, shape,
                                   dtype))
    return jax.tree_util.tree_unflatten(treedef, outs)


@functools.lru_cache(maxsize=None)
def _programs(treedef, entries, fsegs=None):
    """Jitted store programs, cached per (tree structure, segment
    layout, quantization layout) so every store over the same model
    family shares compiled code — a fresh store per run costs zero
    recompiles.

    Every program takes the store's row-segment buffers as ONE tuple
    ``bufs``: ``(fbuf, ibuf)`` for the f32 store, or ``(qbuf int8,
    mbuf f32 scale/zp, ibuf)`` when ``fsegs`` — the static float-leaf
    ``(offset, size)`` layout — selects the int8 quantized format.
    Donating the tuple donates every buffer in it, and the f32 traced
    computation is textually unchanged from the two-argument form, so
    ``quant_bits=32`` stays byte-for-byte the existing path."""

    quant = fsegs is not None

    def flatten_impl(tree):
        return _to_rows(tree, entries)

    def unflatten_impl(frow, irow):
        return _from_rows(frow, irow, treedef, entries)

    def quantize_impl(frow, ef):
        # One (Pf,) row + (K, Pf) per-client error-feedback residuals
        # -> per-client int8 rows, scale/zp meta, the quantized inputs
        # x and their dequantized rows dq(q(x)).  Every reduction
        # inside is a per-segment min/max (order-independent), so the
        # produced bits cannot depend on K or on which program traced
        # this.  The residual x - dq(q(x)) is taken on host: traced,
        # x - (q + snap) * scale is an a - b*c that XLA may contract
        # into one FMA.
        x = frow[None, :] + ef
        qrows, mrows = quantize_rows(x, fsegs)
        return qrows, mrows, x, dequantize_rows(qrows, mrows, fsegs)

    def gather_impl(bufs, ids):
        # f32 stores only; quantized stores gather via read_rows ->
        # from_rows so dequantization has ONE compilation unit for
        # every residency layout (see the class gather docstring).
        fbuf, ibuf = bufs
        return _from_stacked_rows(fbuf[ids], ibuf[ids], treedef, entries)

    def gather_one_impl(bufs, i):
        if quant:
            qbuf, mbuf, ibuf = bufs
            return _from_quant_rows(qbuf[i], mbuf[i], ibuf[i], (),
                                    treedef, entries, fsegs)
        fbuf, ibuf = bufs
        return _from_rows(fbuf[i], ibuf[i], treedef, entries)

    def from_rows_impl(*blocks):
        # stacked pytree straight from materialized row blocks — the
        # tiered store's mixed hot/cold gather (rows assembled on host)
        if quant:
            qrows, mrows, irows = blocks
            return _from_quant_rows(qrows, mrows, irows,
                                    (qrows.shape[0],), treedef, entries,
                                    fsegs)
        frows, irows = blocks
        return _from_stacked_rows(frows, irows, treedef, entries)

    def read_rows_impl(bufs, ids):
        # raw row blocks (write-behind demotion reads these before the
        # slots are reused); never donated — it only reads.  Quantized
        # rows move between tiers as their stored int8/meta bits —
        # residency traffic never re-quantizes.
        return tuple(b[ids] for b in bufs)

    def write_rows_impl(bufs, ids, blocks):
        # per-row block write (host->device promotion)
        return tuple(b.at[ids].set(r) for b, r in zip(bufs, blocks))

    def scatter_impl(bufs, ids, frow, irow):
        # f32 stores only; quantized stores go quantize -> write_q so
        # the quantization math lives in ONE compilation unit (see the
        # class scatter docstring).
        fbuf, ibuf = bufs
        return fbuf.at[ids].set(frow), ibuf.at[ids].set(irow)

    def scatter_params_impl(bufs, ids, params):
        frow, irow = flatten_impl(params)
        fbuf, ibuf = bufs
        return ((fbuf.at[ids].set(frow), ibuf.at[ids].set(irow)),
                frow, irow)

    def write_q_impl(bufs, ids, qrows, mrows, irow):
        # quantized-store row write: per-client int8/meta blocks from
        # the standalone quantize program, one shared int32 sidecar row
        qbuf, mbuf, ibuf = bufs
        return (qbuf.at[ids].set(qrows), mbuf.at[ids].set(mrows),
                ibuf.at[ids].set(irow))

    def init_impl(params, rows):
        frow, irow = flatten_impl(params)
        if quant:
            qrow, mrow, _, _ = quantize_impl(
                frow, jnp.zeros((1,) + frow.shape, jnp.float32))
            return (jnp.tile(qrow, (rows, 1)), jnp.tile(mrow, (rows, 1)),
                    jnp.tile(irow[None], (rows, 1)))
        return (jnp.tile(frow[None], (rows, 1)),
                jnp.tile(irow[None], (rows, 1)))

    dk = dict(donate_argnums=(0,))
    return SimpleNamespace(
        flatten=jax.jit(flatten_impl),
        unflatten=jax.jit(unflatten_impl),
        quantize=jax.jit(quantize_impl) if quant else None,
        gather=None if quant else jax.jit(gather_impl),
        gather_one=jax.jit(gather_one_impl),
        from_rows=jax.jit(from_rows_impl),
        read_rows=jax.jit(read_rows_impl),
        write_rows=jax.jit(write_rows_impl, **dk),
        scatter=None if quant else jax.jit(scatter_impl, **dk),
        scatter_params=None if quant else jax.jit(scatter_params_impl,
                                                  **dk),
        write_q=jax.jit(write_q_impl, **dk) if quant else None,
        init=jax.jit(init_impl, static_argnums=(1,)),
    )


class ClientStateStore:
    """All N client model snapshots as one device-resident (N, Pf) f32
    buffer plus an optional (N, Pi) int32 sidecar for non-float leaves.
    One instance per run; it owns the buffers (see the donation
    contract in the module docstring)."""

    def __init__(self, template_params, n_clients: int, *, mesh=None,
                 quant_bits: int = 32, error_feedback: bool = True):
        if n_clients < 1:
            raise ValueError(f"need at least one client, got {n_clients}")
        if int(quant_bits) not in (8, 32):
            raise ValueError(
                f"quant_bits must be 8 or 32, got {quant_bits}")
        treedef, spec, _ = tree_spec(template_params)
        self.treedef, self.spec = treedef, spec
        self.entries, self.p, self.pi = _segment_entries(spec)
        self.n = int(n_clients)
        self.mesh = mesh if (mesh is not None and int(mesh.size) > 1) \
            else None
        self.quant_bits = int(quant_bits)
        if self.quant_bits == 8:
            if self.mesh is not None:
                raise ValueError("quant_bits=8 does not compose with a "
                                 "sharded client mesh yet")
            if self.p == 0:
                raise ValueError("quant_bits=8 needs at least one float "
                                 "leaf to quantize")
        self._fsegs = _float_segs(self.entries) \
            if self.quant_bits == 8 else None
        # error feedback only means anything when quantizing; the
        # residual of an exact f32 write is identically zero.
        self.error_feedback = bool(error_feedback) and self.quant_bits == 8
        # client id -> (Pf,) f32 quantization residual, sparse (only
        # clients that have been written).  Models state a real
        # deployment keeps at the CLIENT, so bytes_by_tier() reports it
        # separately from the store's own row bytes.
        self._ef = {}
        self.rows = self._buffer_rows()
        # dense: every client's authoritative row lives on device.  The
        # tiered subclass overrides this tag ("tiered-host"/"tiered-disk").
        self.residency = "dense"
        # the row writes donate their buffers on every backend, so the
        # CPU tests run the same programs as the chip
        obs.TEL.inc("store.donation_active")
        self._fns = _programs(treedef, self.entries, self._fsegs)
        bufs = self._fns.init(template_params, self.rows)
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            rows_sharded = NamedSharding(self.mesh,
                                         P(self.mesh.axis_names[0]))
            bufs = tuple(jax.device_put(b, rows_sharded) for b in bufs)
        self.bufs = tuple(bufs)

    def _buffer_rows(self) -> int:
        """Height of the device-resident buffer (subclass hook: the
        tiered store allocates only its hot capacity)."""
        if self.mesh is not None:
            from repro.distributed.plan import ClientShardingPlan
            return ClientShardingPlan.for_cohort(self.n, self.mesh).padded_n
        return self.n

    @staticmethod
    def _ids(ids) -> jnp.ndarray:
        return jnp.asarray(np.asarray(ids, np.int32))

    def _rows_of(self, flat):
        """Public row value -> (frow, irow) pair.  Stores WITH a
        sidecar exchange ``(frow, irow)`` tuples; all-float stores keep
        the PR 4 plain-(P,) row convention."""
        if self.pi:
            frow, irow = flat
            return frow, irow
        return flat, jnp.zeros((0,), jnp.int32)

    def _row_value(self, frow, irow):
        return (frow, irow) if self.pi else frow

    # -- error-feedback residuals ---------------------------------------
    def _ef_block(self, ids):
        """(K, Pf) residual block for ``ids`` row-aligned with the
        scatter: each written client's last residual, zeros for clients
        never written (and everywhere when EF is off — the programs
        share one signature either way)."""
        k = len(ids)
        if not self.error_feedback or not self._ef:
            return jnp.zeros((k, self.p), jnp.float32)
        out = np.zeros((k, self.p), np.float32)
        for j, c in enumerate(ids):
            r = self._ef.get(int(c))
            if r is not None:
                out[j] = r
        return jnp.asarray(out)

    def _ef_update(self, ids, x, dq):
        """Store the (K, Pf) residuals ``x - dq`` of the quantized
        inputs ``x`` and their dequantized rows ``dq``, subtracted here
        in numpy (one rounding, no FMA).  Duplicate ids carried
        identical inputs, so last-write-wins is exact."""
        if not self.error_feedback:
            return
        arr = np.asarray(x, np.float32) - np.asarray(dq, np.float32)
        for j, c in enumerate(ids):
            self._ef[int(c)] = arr[j]

    def ef_residual(self, client_id: int):
        """One client's current (Pf,) quantization residual, or None if
        that client has never been written (or EF is off)."""
        return self._ef.get(int(client_id))

    # -- byte accounting ------------------------------------------------
    @property
    def wire_bytes_per_update(self) -> int:
        """Modeled uplink bytes of ONE client update in this store's
        row format: int8 segment + f32 scale/zp meta + int32 sidecar
        when quantized, full-width f32 + sidecar otherwise."""
        if self.quant_bits == 8:
            return self.p + 8 * len(self._fsegs) + 4 * self.pi
        return 4 * self.p + 4 * self.pi

    def bytes_by_tier(self):
        """{"hot": device row bytes, "cold": spilled row bytes, "ef":
        error-feedback residual bytes} — ``ef`` is reported separately
        because it models client-side state, not store rows.  Also
        refreshes the ``store.bytes_hot``/``store.bytes_cold`` gauges."""
        out = {"hot": int(sum(b.nbytes for b in self.bufs)),
               "cold": self._cold_nbytes(),
               "ef": 4 * self.p * len(self._ef)}
        obs.TEL.gauge("store.bytes_hot", out["hot"])
        obs.TEL.gauge("store.bytes_cold", out["cold"])
        return out

    def _cold_nbytes(self) -> int:
        return 0  # dense store: everything is hot (tiered overrides)

    # -- flat <-> pytree views ------------------------------------------
    @property
    def buffer(self):
        """The primary (rows, Pf) row buffer — f32, or int8 when
        ``quant_bits=8``.  Read-only by convention — do not hold a
        reference across scatter/merge_scatter (donation)."""
        return self.bufs[0]

    @property
    def int_buffer(self):
        """The (rows, Pi) int32 sidecar (zero-width when the template
        has float leaves only).  Same donation contract as ``buffer``."""
        return self.bufs[-1]

    def flatten(self, params):
        """Model pytree -> flat row (one jitted concat): a (Pf,) f32
        array, or a ``(f32 row, int32 row)`` pair when the template has
        non-float leaves."""
        frow, irow = self._fns.flatten(params)
        return self._row_value(frow, irow)

    def unflatten(self, flat):
        """Flat row (``flatten``'s convention) -> model pytree with
        per-leaf shapes/dtypes."""
        frow, irow = self._rows_of(flat)
        return self._fns.unflatten(frow, irow)

    # -- gather / scatter -----------------------------------------------
    def gather(self, ids: Sequence[int]):
        """-> stacked start-params pytree, leaves (len(ids), ...).

        One device program per ids-length bucket (callers pad cohorts
        — the engine's pow2/mesh convention — to bound retraces).
        Duplicate ids are fine (padded slots repeat the last client).

        Quantized stores dequantize through the ``from_rows`` program
        for EVERY layout (dense, tiered hot, tiered mixed) — one
        compilation unit producing the f32 view (``dq = (q + snap) *
        scale``), so gathered bits cannot depend on residency (XLA may
        fuse float math differently per compilation unit, as it does
        for the merge).
        """
        idl = self._ids(ids)
        if self.quant_bits == 8:
            return self._fns.from_rows(*self._fns.read_rows(self.bufs,
                                                            idl))
        return self._fns.gather(self.bufs, idl)

    def gather_one(self, client_id: int):
        """-> one client's snapshot as a model pytree."""
        return self._fns.gather_one(self.bufs, int(client_id))

    def _quantize_for(self, ids: Sequence[int], frow):
        """Quantize one global row per target client (error-feedback
        residual added back, fresh residual banked); returns the (K,)
        int8/meta row blocks to write.  The quantization ALWAYS runs
        the standalone ``quantize`` program — tracing it into a donated
        write would let XLA contract the dequantize FMA differently per
        buffer height, and the residuals (hence every later write)
        would diverge across residency layouts."""
        qrows, mrows, x, dq = self._fns.quantize(frow, self._ef_block(ids))
        self._ef_update(ids, x, dq)
        return qrows, mrows

    def scatter(self, ids: Sequence[int], flat_global):
        """Write one flat global row into every ``ids`` slot in place
        (donated).  Duplicate ids write the same row — harmless (equal
        error-feedback inputs produce equal quantized rows)."""
        frow, irow = self._rows_of(flat_global)
        idl = self._ids(ids)
        if self.quant_bits == 8:
            qrows, mrows = self._quantize_for(ids, frow)
            self.bufs = self._fns.write_q(self.bufs, idl, qrows, mrows,
                                          irow)
        else:
            self.bufs = self._fns.scatter(self.bufs, idl, frow, irow)

    def scatter_params(self, ids: Sequence[int], params):
        """Flatten ``params`` and scatter it into ``ids``; returns the
        flat row for callers tracking the current global row (always
        the exact f32 row — quantization is internal to the buffers).
        The f32 store fuses flatten+scatter into one program; the
        quantized store dispatches flatten, quantize, write."""
        if self.quant_bits == 8:
            frow, irow = self._fns.flatten(params)
            self.scatter(ids, self._row_value(frow, irow))
            return self._row_value(frow, irow)
        self.bufs, frow, irow = self._fns.scatter_params(
            self.bufs, self._ids(ids), params)
        return self._row_value(frow, irow)

    # -- merge + scatter (the async round-step tail) --------------------
    def merge_scatter(self, ids: Sequence[int], stacked_updates, coef,
                      params, *, use_kernel: bool = False,
                      interpret=None):
        """Fold one drained window into the global model and re-snapshot
        the merged clients.

        ``stacked_updates``: trained cohort pytree, leaves
        (len(ids), ...).  ``coef``: (len(ids)+1,) telescoped merge
        coefficients (``staleness_merge_coefficients`` order: global
        row 0 first) — zero entries (masked stragglers / padded rows)
        contribute exactly nothing.  ``params``: the current global
        model pytree.  ``use_kernel=True`` dispatches the merge through
        the folded Pallas fedagg kernel — the same ``fedagg_fold_pytree``
        program the dict path's ``staleness_weighted_merge(use_kernel=
        True)`` runs.
        Returns ``(new_params, new_global_flat)``.

        The merge ALWAYS dispatches the standalone jitted program the
        dict reference runs (``_merge_folded_jnp`` or the fedagg
        kernel), then scatters through the fused flatten+scatter
        program.  Tracing the merge INSIDE the donated scatter program
        would let XLA re-fuse the reduction per buffer shape (FMA
        contraction differs across compilation units — and across
        buffer HEIGHTS, so a tiered/sharded store could never match
        the dense one).  Two dispatches buy histories that are
        bit-identical to the dict path and across residency layouts by
        construction.
        """
        tel = obs.TEL
        host_coef = np.asarray(coef, np.float32)  # fedlint: disable=FED002 -- coef is the host numpy staleness-coefficient vector, packing not a device readback
        coef = jnp.asarray(host_coef)
        with tel.span("store.merge", rows=len(ids), kernel=use_kernel):
            if use_kernel and self.mesh is not None:
                # XLA cannot partition a Pallas kernel over the
                # row-sharded cohort: reduce per shard + one psum
                from repro.distributed.aggregate import sharded_fold_merge
                new_params = sharded_fold_merge(
                    self.mesh, params, stacked_updates, host_coef,
                    use_kernel=True, interpret=interpret)
            elif use_kernel:
                new_params = fedagg_fold_pytree(params, stacked_updates,
                                                coef, interpret=interpret)
            else:
                new_params = _merge_folded_jnp(params, stacked_updates,
                                               coef)
        with tel.span("store.scatter", rows=len(ids)):
            row = self.scatter_params(ids, new_params)
        return new_params, row


def wire_bytes(params, quant_bits: int = 32) -> int:
    """Modeled uplink bytes of ONE client update for ``params`` under
    the given row format — the store-free companion of
    ``ClientStateStore.wire_bytes_per_update`` (the dict-of-pytrees
    runners use it so ``meta["bytes_up"]`` is comparable across
    snapshot paths)."""
    _, spec, _ = tree_spec(params)
    entries, pf, pi = _segment_entries(spec)
    if int(quant_bits) == 8:
        n_float = sum(1 for kind, *_ in entries if kind == "f")
        return pf + 8 * n_float + 4 * pi
    return 4 * pf + 4 * pi
