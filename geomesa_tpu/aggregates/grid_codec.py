"""Device-side grid readback codecs (sparse + fp16 packing).

≙ reference ``DensityScan`` result encoding (index/iterators/DensityScan.
scala:95-106): the reference ships each server's partial grid as *sparse*
kryo-encoded (cell, weight) pairs because the dense grid dominates the wire
cost back to the client. Here the wire is the device→host readback, so the
pack runs ON DEVICE (one tiny fused kernel after the scatter) and the host
decodes:

- ``sparse``: ``[nnz, count, mass_bits, cell_idx…(cap), fp16 weight pairs]``
  — 6 bytes per nonzero cell. Chosen when the match-count bound says cell
  occupancy stays under ~1/3 (below that it beats the fp16-dense encoding).
- ``fp16``: same header + the full grid as fp16 packed two-per-uint32 —
  2 bytes/cell, half the raw f32 readback, exact for integer cell counts
  up to 2048 (the unweighted case by construction).

Both carry a device-computed f32 ``mass`` in the header; the decoder checks
the decoded sum against it and signals a fallback to the raw f32 grid when
fp16 rounding (huge per-cell weights, inf saturation) would distort the
result. Everything is uint32 on the wire so a render costs exactly ONE
device fetch.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HEADER = 4  # [nnz, count, mass_bits, maxcell_bits]

# decoded f64 sum vs device f32 mass: fp16 carries ~11 mantissa bits, so a
# sum of rounded cells stays within ~2^-10 relative of the true mass; beyond
# that something saturated (inf) or overflowed and the caller must re-fetch
MASS_RTOL = 2e-3


def _fp16_pairs(w: jnp.ndarray) -> jnp.ndarray:
    """(M,) f32 → (ceil(M/2),) uint32 of bit-packed fp16 pairs."""
    h = lax.bitcast_convert_type(w.astype(jnp.float16), jnp.uint16)
    h = h.astype(jnp.uint32)
    if h.shape[0] % 2:
        h = jnp.concatenate([h, jnp.zeros((1,), jnp.uint32)])
    h = h.reshape(-1, 2)
    return h[:, 0] | (h[:, 1] << 16)


def _header(flat: jnp.ndarray, nnz: jnp.ndarray, count: jnp.ndarray):
    mass = jnp.sum(flat, dtype=jnp.float32)
    # max cell rides along so narrow encodings can reject per-cell overflow
    # exactly — a clipped hotspot can be tiny relative to the global mass
    # and would otherwise slip through the mass guard
    peak = jnp.max(flat, initial=0.0).astype(jnp.float32)
    return jnp.stack([
        nnz.astype(jnp.uint32),
        count.astype(jnp.uint32),
        lax.bitcast_convert_type(mass, jnp.uint32),
        lax.bitcast_convert_type(peak, jnp.uint32),
    ])


def pack_sparse(grid: jnp.ndarray, count: jnp.ndarray, cap: int) -> jnp.ndarray:
    """Nonzero cells of an (H, W) f32 grid as one uint32 vector."""
    flat = grid.reshape(-1)
    hw = flat.shape[0]
    nz = flat != 0
    sel = jnp.nonzero(nz, size=cap, fill_value=hw)[0]
    ok = sel < hw
    w = jnp.where(ok, flat[jnp.clip(sel, 0, hw - 1)], 0.0)
    head = _header(flat, jnp.sum(nz), count)
    return jnp.concatenate([head, sel.astype(jnp.uint32), _fp16_pairs(w)])


def pack_fp16(grid: jnp.ndarray, count: jnp.ndarray) -> jnp.ndarray:
    """Whole (H, W) f32 grid as fp16, two cells per uint32."""
    flat = grid.reshape(-1)
    head = _header(flat, jnp.sum(flat != 0), count)
    return jnp.concatenate([head, _fp16_pairs(flat)])


def pack_u8(grid: jnp.ndarray, count: jnp.ndarray) -> jnp.ndarray:
    """Whole (H, W) grid as uint8 cells, four per uint32 — 1 byte/cell,
    exact for integer counts ≤255 (the unweighted-render common case: a
    512² grid reads back as 256KB). Saturated/fractional cells distort the decoded
    sum, which the mass guard catches → caller downgrades encodings."""
    flat = grid.reshape(-1)
    head = _header(flat, jnp.sum(flat != 0), count)
    q = jnp.clip(jnp.rint(flat), 0, 255).astype(jnp.uint32)
    pad = (-q.shape[0]) % 4
    if pad:
        q = jnp.concatenate([q, jnp.zeros((pad,), jnp.uint32)])
    q = q.reshape(-1, 4)
    body = q[:, 0] | (q[:, 1] << 8) | (q[:, 2] << 16) | (q[:, 3] << 24)
    return jnp.concatenate([head, body])


def _unpack_fp16_pairs(u: np.ndarray, m: int) -> np.ndarray:
    h = np.empty(u.size * 2, np.uint16)
    h[0::2] = (u & 0xFFFF).astype(np.uint16)
    h[1::2] = (u >> 16).astype(np.uint16)
    return h[:m].view(np.float16).astype(np.float32)


def _f32_bits(word) -> float:
    return float(np.array([word], dtype=np.uint32).view(np.float32)[0])


def decode(packed: np.ndarray, mode: str, cap: Optional[int],
           height: int, width: int
           ) -> Optional[Tuple[np.ndarray, int, float]]:
    """Packed uint32 vector → ((H, W) f32 grid, count, mass), or ``None``
    when the encoding can't represent the result faithfully (sparse cap
    overflow, u8/fp16 per-cell overflow, rounding drift past the mass
    guard) and the caller should step down the encoding ladder."""
    packed = np.asarray(packed, dtype=np.uint32)
    nnz = int(packed[0])
    count = int(packed[1])
    mass = _f32_bits(packed[2])
    peak = _f32_bits(packed[3])
    if mode == "u8" and peak > 255.0:
        return None  # a clipped hotspot may be tiny vs the global mass
    if mode == "fp16" and peak > 65504.0:
        return None  # fp16 saturates to inf
    grid = np.zeros((height, width), dtype=np.float32)
    hw = height * width
    if mode == "sparse":
        if nnz > cap:
            return None
        idx = packed[HEADER: HEADER + nnz].astype(np.int64)
        w = _unpack_fp16_pairs(packed[HEADER + cap:], cap)[:nnz]
        grid.reshape(-1)[idx] = w
    elif mode == "u8":
        body = packed[HEADER:]
        cells = np.empty(body.size * 4, np.uint8)
        cells[0::4] = body & 0xFF
        cells[1::4] = (body >> 8) & 0xFF
        cells[2::4] = (body >> 16) & 0xFF
        cells[3::4] = (body >> 24) & 0xFF
        grid = cells[:hw].astype(np.float32).reshape(height, width)
    else:
        grid = _unpack_fp16_pairs(packed[HEADER:], hw).reshape(height, width)
    got = float(grid.sum(dtype=np.float64))
    if not np.isfinite(got) or abs(got - mass) > MASS_RTOL * max(abs(mass), 1.0):
        return None
    return grid, count, mass


def choose(count_bound: int, height: int, width: int, mode: str = "auto",
           unit_weights: bool = False) -> list:
    """Encoding ladder (cheapest wire cost first) from a bound on the number
    of matched rows (nnz ≤ min(matches, cells)). Each entry is
    (mode, sparse_cap); the caller walks down the ladder when a decode
    reports it couldn't carry the result, ending at raw f32 readback.
    ``unit_weights`` admits the u8 encoding (exact only for integer counts
    ≤255/cell)."""
    if mode == "none":
        return []
    if mode not in ("auto", "sparse", "fp16", "u8"):
        mode = "auto"  # malformed knob values fall back (reference behavior)
    if mode == "u8" and not unit_weights:
        # u8 per-cell rounding of fractional weights can cancel in the mass
        # guard while individual cells are off by up to 0.5 — not faithful
        mode = "fp16"
    hw = height * width
    nnzb = max(1, min(int(count_bound), hw))
    cap = 1 << max(5, (nnzb - 1).bit_length())
    if mode != "auto":
        return [(mode, cap if mode == "sparse" else None)]
    ladder = [("sparse", cap), ("fp16", None)]
    if unit_weights:
        ladder.insert(0, ("u8", None))
    # an encoding that ships more bytes than the raw f32 grid (sparse cap at
    # high occupancy) is strictly worse than falling straight to raw
    ladder = [mc for mc in ladder
              if packed_bytes(mc[0], mc[1], height, width) < 4 * hw]
    ladder.sort(key=lambda mc: packed_bytes(mc[0], mc[1], height, width))
    return ladder


def packed_bytes(mode: str, cap: Optional[int], height: int, width: int) -> int:
    hw = height * width
    if mode == "sparse":
        return 4 * (HEADER + cap + (cap + 1) // 2)
    if mode == "u8":
        return 4 * (HEADER + (hw + 3) // 4)
    return 4 * (HEADER + (hw + 1) // 2)


PACK_FNS = {"sparse": pack_sparse, "fp16": pack_fp16, "u8": pack_u8}

_PACK_JITS: dict = {}


def pack_jit(mode: str, cap: Optional[int]):
    """Jitted pack fn cached per (mode, cap) — a fresh jax.jit closure per
    prepared query would retrace/recompile the identical kernel every
    time."""
    key = (mode, cap)
    if key not in _PACK_JITS:
        base = PACK_FNS[mode]
        if mode == "sparse":
            _PACK_JITS[key] = jax.jit(
                lambda g, c, _b=base, _p=cap: _b(g, c, _p))
        else:
            _PACK_JITS[key] = jax.jit(base)
    return _PACK_JITS[key]
