"""The doubly-periodic box's transform pair as matmuls on the MXU.

A real 2-D discrete HARTLEY transform, H[k] = sum_j x[j] cas(2 pi j k / n)
with cas = cos + sin, applied along each axis in turn. It diagonalizes
the wrap second difference exactly as the real FFT does — cas(2 pi k j / n)
lies in the span of e^{+-2 pi i k j / n}, both with the eigenvalue
lam(k) = lam(n - k) = 2 cos(2 pi k / n) - 2 — but stays REAL: no complex
array on the device, and the transform is its own inverse (H H = n I).

Why matmuls and not ``jnp.fft``: on a TPU v5e the XLA transform pair of
an 8192^2 f32 field took 36.4 ms — 24 complex stage products (22.8 ms)
and the staging of their operands (pads, reverses, copies: 13.6 ms) —
where this real pair takes 10.7 ms: 8 stage products (6.7 ms) and four
relayouts (``poisson.dct_neumann_operators`` made the same move for the
forest's coarse solve). A dense 8192-wide factor would be 268 MB and
32x the arithmetic, so a length n = n1 * n2 with both factors <= 256 runs as
two stages of the four-step factorization (``AxisDHT``): stage A one
[n1, n1] cas matrix, stage B the twiddled [2 n2, 2 n2] matrices batched
over n1 / 2 pairs of rows. A length <= 256 is one dense stage; a length
with no such split is left to the caller (``HartleyPlan2D.build``
returns None).

The spectrum comes out in digit-reversed order (``AxisDHT.freq`` names
each slot's frequency): the spectral divide is pointwise and the
inverse reads that order directly, so no permutation runs on the device.
Every contraction runs at ``Precision.HIGHEST``: the default single bf16
pass would be a lower precision than the field's f32. Each axis is
transformed as the leading axis after the member axes, with every other
axis riding the matmuls' free dimensions; the x axis gets there by one
transpose each way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# the longest length one dense stage takes, and the bound on each factor
# of a two-stage length (two MXU tiles a side)
MAX_STAGE = 256


def _tiles(m: int) -> int:
    return -(-m // 128)


def split(n: int):
    """How a length runs: ``()`` for one dense stage (n <= 256),
    ``(n1, n2)`` for two stages, ``None`` where no factorization into
    parts <= 256 with an even n1 exists (the caller keeps the XLA
    transform). Among the factorizations, the fewest 128-wide MXU
    passes per column of the rest: stage A streams n2 columns through
    an [n1, n1] matrix, stage B n1 / 2 batches through [2 n2, 2 n2]; a
    tie takes the larger n2 (the reshape's second-minor digit)."""
    if n <= MAX_STAGE:
        return ()
    best = None
    for n1 in range(2, MAX_STAGE + 1, 2):
        n2, rem = divmod(n, n1)
        if rem or n2 > MAX_STAGE:
            continue
        passes = n2 * _tiles(n1) ** 2 + n1 // 2 * _tiles(2 * n2) ** 2
        key = (passes, -n2)
        if best is None or key < best[0]:
            best = (key, (n1, n2))
    return None if best is None else best[1]


def _cas(theta):
    return np.cos(theta) + np.sin(theta)


class AxisDHT:
    """One axis's Hartley transform: host-built f64 constants stored at
    the plan's dtype, applied to arrays whose LEADING axis has length n.

    Two stages (n = n1 * n2, j = n2 j1 + j2, k = k1 + n1 k2): stage A
    is the length-n1 Hartley transform T[k1, j2] of each column j2; for
    a real input the complex DFT it stands for is Z[k1] = (T[k1] +
    T[-k1]) / 2 - i (T[k1] - T[-k1]) / 2, so stage B, the twiddled
    length-n2 transform, needs the PAIR (k1, n1 - k1) and gives both
    members' outputs:
    H[k1 + n1 k2] = sum_j2 cos(phi) T[k1, j2] + sin(phi) T[n1 - k1, j2],
    phi = 2 pi j2 (k1 + n1 k2) / n. Stage A's rows come out pair by
    pair (0 with n1 / 2, then p with n1 - p), so stage B is one matmul
    of [2 n2, 2 n2] batched over the n1 / 2 pairs, and every
    intermediate is real and the size of the field."""

    def __init__(self, n: int, dtype, factors):
        self.n = n
        self.factors = factors
        if not factors:
            jk = np.outer(np.arange(n), np.arange(n)) % n
            self.w = jnp.asarray(_cas(2.0 * np.pi * jk / n), dtype)
            self.freq = np.arange(n)
            return
        n1, n2 = factors
        half = n1 // 2
        # k1 of stage A's row (p, m): the pair p, its member m
        pm = np.arange(half)
        k1 = np.stack([pm, (n1 - pm) % n1], axis=1)
        k1[0, 1] = half
        a = _cas(2.0 * np.pi * (k1[:, :, None] * np.arange(n1) % n1) / n1)
        a = a.reshape(n1, n1)
        # stage B: [p, m, k2, m', j2]
        k = k1[:, :, None] + n1 * np.arange(n2)          # [p, m, k2]
        phi = 2.0 * np.pi * (k[..., None] * np.arange(n2) % n) / n
        b = np.zeros((half, 2, n2, 2, n2))
        for m in range(2):
            b[:, m, :, m, :] = np.cos(phi[:, m])
            b[:, m, :, 1 - m, :] = np.sin(phi[:, m])
        # the self-paired 0 and n1 / 2 read only themselves: cas
        b[0] = 0.0
        for m in range(2):
            b[0, m, :, m, :] = _cas(phi[0, m])
        # the inverse runs the transposed stages in reverse order; each
        # matrix is stored in both orientations, so that every product
        # contracts the weights' last axes (a member-batched transform
        # then computes each member exactly as a solo one)
        self.a, self.at = (jnp.asarray(w, dtype) for w in (a, a.T))
        self.b, self.bt = (jnp.asarray(w, dtype)
                           for w in (b, b.transpose(0, 3, 4, 1, 2)))
        # slot (p, m, k2) holds frequency k1(p, m) + n1 * k2
        self.freq = k.reshape(-1)

    def label(self) -> str:
        return "x".join(map(str, self.factors)) if self.factors \
            else str(self.n)

    def forward(self, d: jnp.ndarray, nb: int = 0) -> jnp.ndarray:
        """``[*lead, n, ...]`` natural order -> the same shape, the axis
        after the ``nb`` leading (member) axes in slot order. A member
        axis is a BATCH dimension of every matmul, so each member's
        products have exactly the shapes of a solo transform."""
        if not self.factors:
            return _dot(self.w, d, nb, 1, 0)
        lead, rest = d.shape[:nb], d.shape[nb + 1:]
        t = _dot(self.a, d.reshape(*lead, *self.factors, *rest), nb, 1, 0)
        return _pairs(self.b, t, nb).reshape(*lead, self.n, *rest)

    def inverse(self, s: jnp.ndarray, nb: int = 0,
                last: bool = False) -> jnp.ndarray:
        """The transposed stages in reverse order: the Hartley matrix is
        symmetric, so this is H again (unnormalized), from the slot
        order back to natural order. ``last`` returns the axis moved
        to the end instead; moving stage A's two output digits there
        together lets XLA fold the relayout of that stage's output into
        the one transpose (one copy where two ran)."""
        if not self.factors:
            x = _dot(self.w, s, nb, 1, 0)
            return jnp.moveaxis(x, nb, -1) if last else x
        lead, rest = s.shape[:nb], s.shape[nb + 1:]
        t = _pairs(self.bt, s.reshape(*lead, *self.factors, *rest), nb)
        x = _dot(self.at, t, nb, 1, 0)                 # [..., j1, j2, ...]
        if last:
            x = jnp.moveaxis(jnp.moveaxis(x, nb, -1), nb, -1)
            return x.reshape(*lead, *rest, self.n)
        return x.reshape(*lead, self.n, *rest)


def _pairs(w, t, nb):
    """The pair stage: ``w`` [p, m, k2, m', j2] against ``t``
    [..., (p, m'), j2, ...] batched over the pairs p, giving
    [..., (p, m), k2, ...]."""
    lead, (n1, n2), rest = t.shape[:nb], t.shape[nb:nb + 2], t.shape[nb + 2:]
    s = _dot(w, t.reshape(*lead, n1 // 2, 2, n2, *rest), nb, (3, 4), (1, 2),
             wb=(0,))
    return s.reshape(*lead, n1, n2, *rest)


def _dot(w, d, nb, wc, dc, wb=()):
    """``w``'s axes ``wc`` against ``d``'s axes ``dc`` (``d``'s counted
    after its ``nb`` member axes, over which ``w`` is broadcast),
    batched over the member axes and then over ``wb`` of both. Output:
    members, batch, ``w``'s free axes, ``d``'s free axes."""
    lead = d.shape[:nb]
    if lead:
        w = jnp.broadcast_to(w, lead + w.shape)
    wc = (wc,) if isinstance(wc, int) else wc
    dc = (dc,) if isinstance(dc, int) else dc
    batch = tuple(range(nb)) + tuple(nb + i for i in wb)
    return jax.lax.dot_general(
        w, d, ((tuple(nb + i for i in wc), tuple(nb + i for i in dc)),
               (batch, batch)),
        precision=jax.lax.Precision.HIGHEST)


class HartleyPlan2D:
    """The 2-D transform over the last two axes of ``[..., ny, nx]``,
    built only where both lengths factor (``build`` returns None
    otherwise). The spectrum's layout is ``[..., nx', ny']``: the
    input's leading (member) axes, then x, then y, both in slot order
    (``x.freq`` / ``y.freq`` name each slot's frequency)."""

    def __init__(self, ny: int, nx: int, dtype, fy, fx):
        self.y = AxisDHT(ny, dtype, fy)
        self.x = AxisDHT(nx, dtype, fx)

    @classmethod
    def build(cls, ny: int, nx: int, dtype):
        fy, fx = split(ny), split(nx)
        if fy is None or fx is None:
            return None
        return cls(ny, nx, dtype, fy, fx)

    def note(self) -> str:
        return f"mxu,y={self.y.label()},x={self.x.label()}"

    def forward(self, b: jnp.ndarray) -> jnp.ndarray:
        nb = b.ndim - 2
        d = self.y.forward(b, nb)                          # [..., ny', nx]
        return self.x.forward(jnp.moveaxis(d, -1, nb), nb)  # [..., nx', ny']

    def inverse(self, s: jnp.ndarray) -> jnp.ndarray:
        """Unnormalized: inverse(forward(b)) = ny * nx * b."""
        nb = s.ndim - 2
        return self.y.inverse(self.x.inverse(s, nb, last=True), nb)
