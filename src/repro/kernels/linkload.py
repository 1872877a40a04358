"""Pallas TPU kernels for the switch dataplane step (netsim hot-spot).

Computes per-link offered load from (sub-flow -> link) incidence plus the
queue update and RED/ECN mark probabilities — the per-step work of every
ToR/spine in the fluid simulator.

TPU adaptation: the scatter-add over link ids is reformulated as a
ONE-HOT MATMUL so it runs on the MXU instead of serial scatter ports.
Sub-flows stream through the grid in ``block_n`` tiles with the flow axis
on the 128-wide lane axis; for each tile the kernel builds a TRANSPOSED
one-hot ``oh[rows, block_n]`` (links on sublanes) by a broadcasted_iota
comparison.  Then

  * load  = rates[1, bn] . oh^T  -> [1, rows]   (scatter-add)
  * gather = scale[1, rows] @ oh -> [1, bn]      (per-flow link value)

Every operand is 2-D, every per-link vector is a ``[1, L_pad]`` row and
every per-flow vector a ``[rows, n]`` array blocked on lanes, so each
block matches XLA's tiling.  The dots run at ``Precision.HIGHEST``: rates
are f32 bps near 1e11, and a single bf16 pass would round them on the chip
only (the one-hot side is exact in any precision).  ``n_links`` is padded
to lanes (128) with one spare column, the sentinel for absent hops.

Bands: each pass of the tiered cascade touches one tier of the fabric, and
a topology lays each tier's link ids out contiguously
(``Topology.bands``: host tx, each fabric hop, host rx).  So a pass builds
its one-hot over its own band only, ``rows`` = the band's width padded to
lanes, in band-local ids (row i is link ``lo + i``), and adds its load to
the full arrival row at the band's offset.  An id off the band (the
sentinel of an absent hop, a padded flow) matches no row and gathers a
factor of 1.  Without bands every pass spans all ``n_links``.

VMEM: the one-hot and its iota are ``rows * block_n * 4`` bytes each; at
``three_tier()`` width (2080 links) the widest band has 512 rows, 0.5 MB
each at ``block_n=256`` (2.2 MB over all 2176 padded links without bands),
inside the default scoped-VMEM limit.

Oracle: kernels/ref.py::linkload_cascade_tiered_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _onehot(lid_row: jax.Array, n_rows: int) -> jax.Array:
    """f32[n_rows, bn]: oh[l, i] = (lid_row[0, i] == l).  lid_row i32[1, bn]."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (n_rows, lid_row.shape[-1]), 0)
    return (iota == lid_row).astype(jnp.float32)


def _scatter(r_row: jax.Array, oh: jax.Array) -> jax.Array:
    """[1, bn] x [rows, bn] -> [1, rows]: per-link sum of the row."""
    return jax.lax.dot_general(
        r_row, oh, (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)


def _gather(s_row: jax.Array, oh: jax.Array) -> jax.Array:
    """[1, rows] @ [rows, bn] -> [1, bn]: each flow's link value."""
    return jnp.dot(s_row, oh, precision=_HI, preferred_element_type=jnp.float32)


def _red_mark(newq, kmin, kmax, pmax):
    ramp = (newq - kmin) / (kmax - kmin)
    return jnp.where(newq < kmin, 0.0, jnp.where(newq > kmax, 1.0, ramp * pmax))


def _row(x: jax.Array, width: int, fill: float = 0.0) -> jax.Array:
    """f32[n] -> f32[1, width] (padded with ``fill``)."""
    x = x.astype(jnp.float32)
    return jnp.pad(x, (0, width - x.shape[0]), constant_values=fill)[None, :]


def _lanes(x: int) -> int:
    """``x`` rounded up to whole 128-wide lane tiles."""
    return -(-x // 128) * 128


def _pad_links(n_links: int) -> int:
    return _lanes(n_links + 1)


def _band_width(band: tuple[int, int]) -> int:
    """Rows of a pass's one-hot: its band of link ids padded to lanes."""
    lo, hi = band
    return _lanes(hi - lo)


def onehot_rows(bands, n_sub: int, hf: int, n_links: int) -> tuple[int, int]:
    """(rows, full): the one-hot rows one tile of ``linkload_cascade_tiered``
    builds over all its passes with ``bands``, and the rows it would build
    with every one-hot over all ``_pad_links(n_links)`` link ids.  Each NIC
    pass builds one one-hot per tile and each fabric pass one per sub-flow
    row, once for its load and once more for its scale gather."""
    bands = _full_bands(bands, hf, n_links)
    per_pass = [1] + [n_sub] * hf + [1]
    rows = 2 * sum(k * _band_width(b) for k, b in zip(per_pass, bands))
    return rows, 2 * sum(per_pass) * _pad_links(n_links)


def _full_bands(bands, hf: int, n_links: int) -> tuple[tuple[int, int], ...]:
    """``bands`` checked, or one band of every link id per pass if None."""
    if bands is None:
        return ((0, n_links),) * (hf + 2)
    bands = tuple((int(lo), int(hi)) for lo, hi in bands)
    assert len(bands) == hf + 2, (bands, hf)
    assert all(0 <= lo < hi <= n_links for lo, hi in bands), (bands, n_links)
    return bands


def _cascade_tiered_kernel(
    fab_ref, tx_ref, rx_ref, rate_ref, queue_ref, cap_ref, qmask_ref,
    arrival_ref, newq_ref, mark_ref, thr_ref, scales_ref, r_ref,
    *, bands, kmin, kmax, pmax, dt, qmax,
):
    """NIC-tiered cascade (netsim/dataplane.cascade_nic).  Grid =
    (hf + 3, n_tiles), pass-major:

      pass 0        host_tx — the N sub-flows of a flow share the NIC, so
                    rates pre-reduce over N and one one-hot serves the tile
      pass 1..hf    fabric hop p-1, one one-hot per sub-flow row
      pass hf+1     host_rx — pre-reduced again
      pass hf+2     apply the rx scale -> thr, fuse queue + RED mark

    Pass k < hf + 2 sees only link ids in its band ``bands[k] = (lo, hi)``,
    so its one-hots and its row of ``scales_ref`` are band-local: row i is
    link ``lo + i``, over ``_band_width`` rows.  Each pass first advances
    the running [N, block_n] rate scratch ``r_ref[t]`` by the PREVIOUS
    pass's scale (gathered through tx for pass 1, per sub-flow through the
    fabric one-hot for passes 2..hf+1, through rx for the final pass); an
    id off that pass's band (an absent hop's sentinel) gathers a factor of
    1.  ``scales_ref[k]`` holds pass k's link load until the last tile adds
    it to the arrival row at its band's offset and converts it in place to
    the capacity scale."""
    p = pl.program_id(0)
    t = pl.program_id(1)
    n_tiles = pl.num_programs(1)
    n_sub = rate_ref.shape[0]
    hf = len(bands) - 2

    def onehot(k, lid_row):
        return _onehot(lid_row - bands[k][0], _band_width(bands[k]))

    def factor(k, lid_row):
        """[1, bn]: pass k's scale at each flow's link, 1 off its band."""
        lo, hi = bands[k]
        s = _gather(scales_ref[k, :, :_band_width(bands[k])], onehot(k, lid_row))
        return jnp.where((lid_row >= lo) & (lid_row < hi), s, 1.0)

    @pl.when((p == 0) & (t == 0))
    def _init():
        arrival_ref[...] = jnp.zeros_like(arrival_ref)

    # ---- advance the running rates by the previous pass's scale ----
    @pl.when(p == 0)
    def _r_fresh():
        r_ref[t] = rate_ref[...]

    @pl.when(p == 1)
    def _r_tx():
        r_ref[t] = r_ref[t] * factor(0, tx_ref[...])

    for k in range(1, hf + 1):
        @pl.when(p == k + 1)
        def _r_fab(k=k):
            stored = r_ref[t]
            lids = fab_ref[k - 1]
            for j in range(n_sub):
                r_ref[t, j:j + 1, :] = stored[j:j + 1] * factor(k, lids[j:j + 1])

    # ---- accumulate pass k's band-local link load into scales_ref[k] ----
    def _acc(k, load_row):
        w = _band_width(bands[k])
        scales_ref[k, :, :w] = jnp.where(t == 0, 0.0, scales_ref[k, :, :w]) + load_row

    @pl.when(p == 0)
    def _load_tx():
        _acc(0, _scatter(jnp.sum(r_ref[t], axis=0, keepdims=True),
                         onehot(0, tx_ref[...])))

    for k in range(1, hf + 1):
        @pl.when(p == k)
        def _load_fab(k=k):
            r = r_ref[t]
            lids = fab_ref[k - 1]
            loads = [_scatter(r[j:j + 1], onehot(k, lids[j:j + 1]))
                     for j in range(n_sub)]
            _acc(k, functools.reduce(jnp.add, loads))

    @pl.when(p == hf + 1)
    def _load_rx():
        _acc(hf + 1, _scatter(jnp.sum(r_ref[t], axis=0, keepdims=True),
                              onehot(hf + 1, rx_ref[...])))

    for k in range(hf + 2):
        @pl.when((p == k) & (t == n_tiles - 1))
        def _finalize_pass(k=k):
            lo = bands[k][0]
            w = _band_width(bands[k])
            # past hi pass k holds no id but the sentinel, cut from the result
            load = scales_ref[k, :, :w]
            arrival_ref[:, lo:lo + w] += load
            scales_ref[k, :, :w] = jnp.minimum(
                1.0, cap_ref[:, lo:lo + w] / jnp.maximum(load, 1.0))

    @pl.when(p == hf + 2)
    def _write_thr():
        thr_ref[...] = r_ref[t] * factor(hf + 1, rx_ref[...])

    @pl.when((p == hf + 2) & (t == n_tiles - 1))
    def _finalize():
        arr = arrival_ref[...]
        newq = jnp.clip(queue_ref[...] + (arr - cap_ref[...]) * dt / 8.0, 0.0, qmax)
        newq = newq * qmask_ref[...]
        newq_ref[...] = newq
        mark_ref[...] = _red_mark(newq, kmin, kmax, pmax)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_links", "bands", "kmin", "kmax", "pmax", "dt", "qmax_bytes",
        "block_n", "interpret"
    ),
)
def linkload_cascade_tiered(
    fab_links: jax.Array,  # i32[n, N, hf]  (-1 = no hop)
    tx_link: jax.Array,  # i32[n]  (-1 = no hop)
    rx_link: jax.Array,  # i32[n]  (-1 = no hop)
    rates: jax.Array,  # f32[n, N]
    queue: jax.Array,  # f32[n_links]
    capacity: jax.Array,  # f32[n_links]
    queue_mask: jax.Array,  # f32[n_links]
    *,
    n_links: int,
    bands: tuple[tuple[int, int], ...] | None = None,
    kmin: float = 400e3,
    kmax: float = 1600e3,
    pmax: float = 0.2,
    dt: float = 10e-6,
    qmax_bytes: float = 8e6,
    block_n: int = 256,
    interpret: bool = False,
):
    """NIC-tiered fused dataplane step: (arrival, new_queue, mark, thr[n, N]).
    Oracle: kernels/ref.py::linkload_cascade_tiered_ref.  ``bands`` (static,
    ``Topology.bands``) gives each of the hf + 2 passes (host tx, the fabric
    hops in order, host rx) the half-open range of link ids it can touch;
    None gives every pass all of ``[0, n_links)``.  ``block_n`` must be a
    multiple of 128 when compiled for the TPU."""
    n, n_sub, hf = fab_links.shape
    bands = _full_bands(bands, hf, n_links)
    dummy = n_links  # absent hops land on the sentinel id, off every band
    pad_n = (-n) % block_n

    def flows(x):  # i32[n, ...] -> sentinel-mapped, padded to n + pad_n
        x = jnp.where(x >= 0, x, dummy).astype(jnp.int32)
        return jnp.pad(x, ((0, pad_n),) + ((0, 0),) * (x.ndim - 1),
                       constant_values=dummy)

    # flow axis last (lanes) everywhere: fab [hf, N, n_pad], rows [1, n_pad]
    fab_t = jnp.transpose(flows(fab_links), (2, 1, 0))
    tx_r = flows(tx_link)[None, :]
    rx_r = flows(rx_link)[None, :]
    rates_t = jnp.pad(rates.astype(jnp.float32), ((0, pad_n), (0, 0))).T
    # per-link rows cover every band's padded one-hot at its offset
    L_pad = max(_pad_links(n_links),
                *(_lanes(b[0] + _band_width(b)) for b in bands))
    n_tiles = (n + pad_n) // block_n
    last = hf + 2
    row = pl.BlockSpec((1, L_pad), lambda p, t: (0, 0))
    flow_row = pl.BlockSpec((1, block_n), lambda p, t: (0, t))
    arrival, newq, mark, thr = pl.pallas_call(
        functools.partial(
            _cascade_tiered_kernel,
            bands=bands, kmin=kmin, kmax=kmax, pmax=pmax, dt=dt, qmax=qmax_bytes,
        ),
        grid=(hf + 3, n_tiles),
        in_specs=[
            pl.BlockSpec((hf, n_sub, block_n), lambda p, t: (0, 0, t)),
            flow_row, flow_row,
            pl.BlockSpec((n_sub, block_n), lambda p, t: (0, t)),
            row, row, row,
        ],
        out_specs=[
            row, row, row,
            # thr is written in the last pass only: park on block 0 before
            # it so no unwritten block is ever copied back to HBM
            pl.BlockSpec((n_sub, block_n),
                         lambda p, t: (0, jnp.where(p == last, t, 0))),
        ],
        out_shape=[jax.ShapeDtypeStruct((1, L_pad), jnp.float32)] * 3 + [
            jax.ShapeDtypeStruct((n_sub, n + pad_n), jnp.float32),
        ],
        scratch_shapes=[
            # per-pass band-local load/scale
            pltpu.VMEM((hf + 2, 1, max(map(_band_width, bands))), jnp.float32),
            pltpu.VMEM((n_tiles, n_sub, block_n), jnp.float32),  # running rates
        ],
        interpret=interpret,
        name="linkload_cascade_tiered",
    )(fab_t, tx_r, rx_r, rates_t, _row(queue, L_pad),
      _row(capacity[:n_links], L_pad, 1e30), _row(queue_mask[:n_links], L_pad))
    return arrival[0, :n_links], newq[0, :n_links], mark[0, :n_links], thr.T[:n]
