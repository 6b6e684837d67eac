"""Plain float32 pieces shared by the references: weights, masks, the cell.

Written from the paper's equations and the repository's documented
contracts, importing nothing of the program:

* weights: the Glorot-uniform draw the models' ``init`` makes from a
  ``jax.random`` key (per layer: ``wx [4, I, H]``, ``wh [4, H, H]``, bias 0
  with the forget gate at 1; dense head uniform, bias 0);
* masks (paper §II-B): one Bernoulli keep-mask per gate, input side and
  hidden side, per MC chain, tied across time.  The bit for (seed, layer,
  kind, gate, row, column) is the murmur3 finaliser of
  ``key ^ mix(row * width + column)``, where ``key`` folds
  (seed, layer, kind, gate) with boost's ``hash_combine``; a column is kept
  when its bits are at least ``round(p * 2**32)``.  A session's chains are
  rows ``k*S .. k*S+S-1`` for the k-th session admitted;
* the LSTM step: ``x`` and ``h`` masked per gate and scaled by ``1/(1-p)``,
  one matmul per gate, everything in float32.

Two matmul precisions: ``"highest"``, float32 products (the reference), and
``"high"``, the three bfloat16 passes a float32 matmul takes at
``Precision.HIGH`` (the control that has to fail the comparison), written
out so that it multiplies the same on every platform.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_GOLDEN = 0x9E3779B9
_M32 = 0xFFFFFFFF


def _mix32(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.uint64) & _M32
    x ^= x >> 16
    x = (x * 0x7FEB352D) & _M32
    x ^= x >> 15
    x = (x * 0x846CA68B) & _M32
    x ^= x >> 16
    return x


def _fold(seed: int, *ids: int) -> int:
    h = int(_mix32(np.uint64(seed & _M32)))
    for k in ids:
        h = h ^ ((int(_mix32(np.uint64(k))) + _GOLDEN + (h << 6) + (h >> 2))
                 & _M32)
        h &= _M32
    return h


def keep_mask(seed: int, layer: int, kind: int, gate: int, rows: np.ndarray,
              width: int, p: float) -> np.ndarray:
    """Boolean keep-mask ``[len(rows), width]`` (kind 0: input, 1: hidden)."""
    key = np.uint64(_fold(seed, layer, kind, gate))
    idx = (rows.astype(np.uint64)[:, None] * np.uint64(width)
           + np.arange(width, dtype=np.uint64)[None, :]) & _M32
    bits = _mix32(key ^ _mix32(idx))
    threshold = min(max(int(round(p * 4294967296.0)), 0), _M32)
    return bits >= threshold


def layer_masks(seed: int, layer: int, rows: np.ndarray, in_dim: int,
                hidden: int, p: float):
    """Float 0/1 masks ``([R, 4, I], [R, 4, H])`` of one Bayesian layer."""
    zx = np.stack([keep_mask(seed, layer, 0, g, rows, in_dim, p)
                   for g in range(4)], axis=1)
    zh = np.stack([keep_mask(seed, layer, 1, g, rows, hidden, p)
                   for g in range(4)], axis=1)
    return zx.astype(np.float32), zh.astype(np.float32)


def init_lstm_stack(key, in_dim: int, hiddens):
    layers, dims = [], [in_dim, *hiddens]
    for d_in, d_h in zip(dims[:-1], dims[1:]):
        key, sub = jax.random.split(key)
        kx, kh = jax.random.split(sub)
        sx = (6.0 / (d_in + d_h)) ** 0.5
        sh = (6.0 / (2 * d_h)) ** 0.5
        wx = jax.random.uniform(kx, (4, d_in, d_h), jnp.float32, -sx, sx)
        wh = jax.random.uniform(kh, (4, d_h, d_h), jnp.float32, -sh, sh)
        b = jnp.zeros((4, d_h), jnp.float32).at[1].set(1.0)
        layers.append((wx, wh, b))
    return layers


def init_dense(key, in_dim: int, out_dim: int):
    s = (6.0 / (in_dim + out_dim)) ** 0.5
    return (jax.random.uniform(key, (in_dim, out_dim), jnp.float32, -s, s),
            jnp.zeros((out_dim,), jnp.float32))


def mdot(a, w, matmul: str):
    """``a @ w`` in float32, at the precision ``matmul`` names.

    ``"high"`` splits each operand into a bfloat16 head and a bfloat16 tail
    and sums the three products that leave out tail times tail.
    """
    dot = functools.partial(jnp.dot, precision=HIGHEST,
                            preferred_element_type=jnp.float32)
    if matmul == "highest":
        return dot(a, w)
    if matmul != "high":
        raise ValueError(f"unknown matmul precision {matmul!r}")

    def split(v):  # rounding that no compiler may drop, unlike a cast pair
        hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        return hi, jax.lax.reduce_precision(v - hi, exponent_bits=8,
                                            mantissa_bits=7)

    (a_hi, a_lo), (w_hi, w_lo) = split(a), split(w)
    return dot(a_hi, w_hi) + (dot(a_hi, w_lo) + dot(a_lo, w_hi))


def lstm_layer(weights, x, h, c, zx, zh, p: float, matmul: str):
    """One layer over a chunk.  ``x [R, T, I]``; returns ``ys, h, c``.

    ``zx``/``zh`` are the float masks or None (a layer without dropout).
    """
    wx, wh, b = weights
    scale = 1.0 / (1.0 - p)

    def step(carry, x_t):
        h, c = carry
        gates = []
        for g in range(4):
            xg, hg = x_t, h
            if zx is not None:
                xg = x_t * zx[:, g] * scale
                hg = h * zh[:, g] * scale
            gates.append(mdot(xg, wx[g], matmul) + mdot(hg, wh[g], matmul)
                         + b[g])
        i, f = jax.nn.sigmoid(gates[0]), jax.nn.sigmoid(gates[1])
        g_, o = jnp.tanh(gates[2]), jax.nn.sigmoid(gates[3])
        c = f * c + i * g_
        h = o * jnp.tanh(c)
        return (h, c), h

    (h, c), ys = jax.lax.scan(step, (h, c), jnp.swapaxes(x, 0, 1))
    return jnp.swapaxes(ys, 0, 1), h, c


def dense(weights, x, matmul: str):
    w, b = weights
    return mdot(x, w, matmul) + b


def session_rows(n_sessions: int, n_samples: int) -> np.ndarray:
    """Mask rows of ``n_sessions`` sessions admitted in order, S chains each."""
    return np.arange(n_sessions * n_samples, dtype=np.uint32)


class Static(dict):
    """A configuration dict usable as a static ``jax.jit`` argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, v) for k, v in self.items()
                                 if isinstance(v, (int, float, str)))))


def bayesian_masks(cfg: dict, seed: int, rows: np.ndarray, dims, first: int):
    """Masks of consecutive layers ``first, first+1, ...`` (None where off)."""
    out, place = [], cfg["placement"]
    for i, (d_in, d_h) in enumerate(zip(dims[:-1], dims[1:])):
        layer = first + i
        if place[layer % len(place)] == "Y" and cfg["p"] > 0:
            out.append(tuple(map(jnp.asarray, layer_masks(
                seed, layer, rows, d_in, d_h, cfg["p"]))))
        else:
            out.append((None, None))
    return out
