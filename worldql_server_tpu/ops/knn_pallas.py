"""Pallas TPU kernel: fused stencil + k-nearest selection for the tick.

The XLA formulation of the tick's neighbor resolve (ops/tick.py steps
4-5) materializes [N, 2K-1] candidate tables (run id, peer, distance,
packed key) in HBM and row-sorts them — several HBM round trips and,
depending on how XLA schedules the slice stack, dozens of kernel
launches. This kernel does the whole resolve in ONE launch: each grid
tile DMAs its sorted-order window (tile + K-1 halo on each side) from
HBM into VMEM, computes the 2K-1 masked squared distances on the VPU,
runs a key-value bitonic sorting network across the window, and writes
the K nearest peer ids straight to the output block.

Contract (identical to the XLA path, ops/tick.py):
* candidates are the ±(K-1) sort-order neighbors with the same run id;
* self and same-peer candidates fall to the ``peer != own`` mask
  (ExceptSelf);
* invalid slots carry the all-ones distance key, so they sink past
  every real candidate — including NaN distances (every NaN bit
  pattern < 0xFFFFFFFF), which therefore still broadcast;
* equal distances tie-break by peer id ascending (the network compares
  (distance bits, peer) lexicographically — same order as the XLA
  path's packed-u64 sort).

Mosaic constraints shape the layout (all measured/verified on v5e):
* everything is 2-D — 1-D selects trip an infinite lowering recursion;
* no 64-bit types inside the kernel (the repo's global x64 mode must
  not leak in — every literal is explicitly 32-bit);
* the sort dimension is the SUBLANE axis: candidates live in a
  [W, tile] matrix built by concatenating [1, tile] window slices, so
  the bitonic exchanges are sublane rolls (slice+concat, natively
  supported; ``pltpu.roll`` currently fails verification here);
* the kernel writes [K, tile] blocks of a transposed [K, N] output and
  the host wrapper transposes back.

Inputs are PADDED sorted columns (run-id pad is -1, so halo lanes
never match). The host wrapper pads N up to the tile multiple and
slices the result back. ``interpret=True`` is chosen automatically off
TPU, so the same kernel body runs under the CPU test suite.
"""

from __future__ import annotations

from functools import partial

from ..spatial import jaxconf  # must precede the jax import
import jax
import jax.numpy as jnp

# u32 all-ones distance sentinel (python int: a module-level jnp scalar
# would be captured as a device constant, which pallas_call rejects)
_INVALID = 0xFFFFFFFF


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def _bitonic_kv(keys, vals):
    """Ascending bitonic sort along axis 0 (sublanes) of
    (keys u32, vals i32), comparing (key, val) lexicographically.
    Axis-0 length must be a power of two. Exchanges are XOR-partner
    rolls — slice+concat under the hood, no gathers, no lane-dim
    reshapes."""
    w = keys.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0)
    size = 2
    while size <= w:
        dist = size // 2
        while dist >= 1:
            up = (row & size) == 0
            left = (row & dist) == 0
            pk = jnp.where(left, jnp.roll(keys, -dist, axis=0),
                           jnp.roll(keys, dist, axis=0))
            pv = jnp.where(left, jnp.roll(vals, -dist, axis=0),
                           jnp.roll(vals, dist, axis=0))
            own_gt = (keys > pk) | ((keys == pk) & (vals > pv))
            par_gt = (pk > keys) | ((pk == keys) & (pv > vals))
            # boolean algebra, not jnp.where: Mosaic rejects a select
            # whose BRANCHES are i1 ("unsupported bitwidth truncation")
            gt = (own_gt & left) | (par_gt & ~left)
            take = gt == up  # in an ascending block the left lane
            keys = jnp.where(take, pk, keys)  # keeps the smaller pair
            vals = jnp.where(take, pv, vals)
            dist //= 2
        size *= 2
    return keys, vals


def _win_size(tile: int, k: int) -> int:
    """Per-tile window: tile + both halos, rounded to the 128-lane
    Mosaic slice alignment. Single source of truth — the kernel's
    window reads and the host wrapper's padding must agree exactly."""
    return -(-(tile + 2 * (k - 1)) // 128) * 128


def _make_kernel(tile: int, k: int, interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w = 2 * k - 1
    wp = _next_pow2(w)
    win = _win_size(tile, k)

    def tile_body(rid_w, peer_w, x_w, y_w, z_w):
        """One tile's resolve from its [1, win] VMEM windows."""

        def at(buf, s):
            # slice [1, tile] at window offset s, re-materialized at
            # lane offset 0 via a roll: Mosaic's concat cannot join
            # operands whose lane offsets differ ("result/input offset
            # mismatch on non-concat dimension"), and the row concat
            # below needs offset-0 operands
            if s == 0:  # a 0-shift roll lowers to an empty slice
                return buf[:, :tile]
            return jnp.roll(buf, -s, axis=1)[:, :tile]

        rid0 = at(rid_w, k - 1)     # [1, tile] self rows
        peer0 = at(peer_w, k - 1)
        x0 = at(x_w, k - 1)
        y0 = at(y_w, k - 1)
        z0 = at(z_w, k - 1)

        key_rows, val_rows = [], []
        for s in range(wp):
            if s < w:
                same = (at(rid_w, s) == rid0) & (at(peer_w, s) != peer0) \
                    & (rid0 >= 0)
                dx = at(x_w, s) - x0
                dy = at(y_w, s) - y0
                dz = at(z_w, s) - z0
                d2 = dx * dx + dy * dy + dz * dz
                key_rows.append(jnp.where(
                    same, jax.lax.bitcast_convert_type(d2, jnp.uint32),
                    jnp.uint32(_INVALID),
                ))
                val_rows.append(
                    jnp.where(same, at(peer_w, s), jnp.int32(-1))
                )
            else:
                key_rows.append(
                    jnp.full((1, tile), _INVALID, jnp.uint32)
                )
                val_rows.append(jnp.full((1, tile), -1, jnp.int32))
        keys = jnp.concatenate(key_rows, axis=0)   # [wp, tile]
        vals = jnp.concatenate(val_rows, axis=0)
        _, vals = _bitonic_kv(keys, vals)
        return vals[:k, :]

    def kernel(rid_ref, peer_ref, x_ref, y_ref, z_ref, out_ref):
        # One program, tiles as an in-kernel loop: this environment's
        # Mosaic fails to legalize ANY grid-ful pallas_call ('func.
        # return'), and a TPU grid is a sequential loop on the core
        # anyway. Inputs are VMEM-resident, so the per-tile window read
        # is a dynamic VMEM slice, not a DMA.
        n_tiles = out_ref.shape[1] // tile

        def body(i, carry):
            start = i * tile
            vals = tile_body(
                rid_ref[:, pl.ds(start, win)],
                peer_ref[:, pl.ds(start, win)],
                x_ref[:, pl.ds(start, win)],
                y_ref[:, pl.ds(start, win)],
                z_ref[:, pl.ds(start, win)],
            )
            out_ref[:, pl.ds(start, tile)] = vals
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_tiles), body,
                          jnp.int32(0))

    def call(rid_p, peer_p, x_p, y_p, z_p, n_pad):
        vm = pl.BlockSpec(memory_space=pltpu.VMEM)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((k, n_pad), jnp.int32),
            in_specs=[vm] * 5,
            out_specs=vm,
            interpret=interpret,
        )(rid_p, peer_p, x_p, y_p, z_p)

    return call


@partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def _knn_jit(rid, peer, pos, k, tile, interpret):
    n = rid.shape[0]
    n_pad = -(-n // tile) * tile
    halo = k - 1
    win = _win_size(tile, k)
    pad = (halo, n_pad - n + win - halo)

    def prep(a, fill=0):
        return jnp.pad(a, pad, constant_values=fill)[None, :]

    cols = (prep(rid, -1), prep(peer, -1),
            prep(pos[:, 0]), prep(pos[:, 1]), prep(pos[:, 2]))

    # chunk the single-program kernel so its VMEM residency (inputs +
    # the [K, chunk] output block) stays a few MB; the last chunk is
    # sized to what remains, not the full stride
    stride = min(n_pad, 64 * tile)
    call = _make_kernel(tile, k, interpret)
    outs = []
    for c0 in range(0, n_pad, stride):
        this = min(stride, n_pad - c0)
        outs.append(call(*(c[:, c0:c0 + this + win] for c in cols), this))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out.T[:n]


def knn_select(rid, peer, pos, *, k: int, tile: int = 512,
               interpret: bool | None = None):
    """[N] run ids (i32, sorted order; -1 = masked row), [N] peers,
    [N, 3] f32 positions → [N, K] nearest co-run peers per row,
    -1-padded, nearest-first. Fused Pallas path; semantically identical
    to the XLA stencil in ops/tick.py."""
    if interpret is None:
        interpret = not jaxconf.on_tpu()
    return _knn_jit(
        rid.astype(jnp.int32), peer.astype(jnp.int32),
        pos.astype(jnp.float32), k, tile, interpret,
    )
