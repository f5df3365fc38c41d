"""One-shot differential privacy for the wire statistics.

The paper's method federates in EXACTLY one round, which makes DP
unusually cheap: iterative FL pays the composition of hundreds of noisy
gradient releases (Abadi et al.'s moments accountant exists to tame
that), while here a single Gaussian-perturbed release of the aggregate
``(G, m_vec)`` — or equivalently of the solved ``W``, since the solve
is post-processing — carries the entire ``(ε, δ)`` budget. No
composition, no amplification bookkeeping: the accountant below is a
running sum that, in the intended use, receives one entry.

Pipeline (policy ``dp``):

1. **Clip** every client's sample rows to L2 norm ``clip``
   (:func:`clip_rows`) — the only data-dependent step, done client-side.
2. **Bound** the per-sample L2 sensitivity of the joint ``(G, m_vec)``
   statistics analytically from the clip bound, the activation's
   ``f'`` range and the label-encoding range (:func:`sensitivity`).
   Add/remove of one sample moves the *aggregate* by at most that — the
   statistics are sums over samples.
3. **Calibrate** the Gaussian scale σ with the exact (Balle & Wang
   2018) Gaussian-mechanism condition via bisection
   (:func:`calibrate_sigma`) — valid at every ε, unlike the classical
   ``σ = Δ√(2 ln(1.25/δ))/ε`` bound, which only holds for ε ≤ 1.
4. **Perturb** once (:func:`noise_stats`): symmetric noise on each
   Gram block (mirrored upper triangle — the AnalyzeGauss scheme), iid
   noise on the moment block. The sample count ``n`` is released
   exactly (bookkeeping; documented in DESIGN.md §10).

``ε = inf`` short-circuits to σ = 0 — clipping still applies, so the
ε-sweep in ``benchmarks/privacy_bench.py`` ends at a bit-exact
clipped-but-noiseless baseline.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import activations as acts
from ..core.solver import ClientStats, GramStats


# ------------------------------------------------------------- clipping
def clip_rows(X, clip: float):
    """Scale each sample row to L2 norm ≤ ``clip`` (host-side, exact
    no-op for rows already inside the ball)."""
    if clip <= 0:
        raise ValueError(f"clip must be > 0, got {clip}")
    X = np.asarray(X)
    if X.size == 0:
        return X
    norms = np.linalg.norm(np.asarray(X, np.float64), axis=1)
    scale = np.minimum(1.0, clip / np.maximum(norms, 1e-300))
    return (X * scale[:, None].astype(X.dtype, copy=False)).astype(
        X.dtype, copy=False)


# ---------------------------------------------------------- sensitivity
def sensitivity(c: int, clip: float, act: str = "logistic",
                *, add_bias: bool = True, target_low: float = 0.05,
                target_high: float = 0.95) -> float:
    """Per-sample L2 sensitivity of the joint ``(G, m_vec)`` statistics.

    One sample ``x`` (clipped, bias appended) contributes
    ``f'_k(d̄)² x xᵀ`` to Gram block ``k`` and ``f'_k(d̄)² d̄_k x`` to
    moment column ``k``. With ``R² = clip² (+1 for the bias)``,
    ``fmax = max f'`` and ``dmax = max |d̄|`` over the label-encoding
    range ``[target_low, target_high]``:

      Δ_G ≤ √k · fmax² · R²,  Δ_m ≤ √c · fmax² · dmax · R,
      Δ   = √(Δ_G² + Δ_m²).

    The bound is feature-dimension-free (the Frobenius norm of the
    rank-1 ``x xᵀ`` is ``‖x‖²`` regardless of width), so it needs only
    the output count and the clip. ``f'`` of the supported activations
    is unimodal with its maximum at the pre-activation 0, so evaluating
    at the interval endpoints plus (clipped-in) 0 is exact, not a grid
    estimate.
    """
    a = acts.get(act)
    if clip <= 0:
        raise ValueError(f"clip must be > 0, got {clip}")
    R2 = clip * clip + (1.0 if add_bias else 0.0)
    R = math.sqrt(R2)
    # the bound must hold for float64 statistics too — evaluate the
    # activation range in x64 (cheap, and an underestimated dmax from
    # a float32 eval would make Δ not an upper bound)
    from ..core.util import enable_x64
    with enable_x64():
        z_lo = float(a.f_inv(jnp.float64(target_low)))
        z_hi = float(a.f_inv(jnp.float64(target_high)))
        z_lo, z_hi = min(z_lo, z_hi), max(z_lo, z_hi)
        zs = [z_lo, z_hi] + ([0.0] if z_lo <= 0.0 <= z_hi else [])
        fmax = max(float(a.f_prime(jnp.float64(z))) for z in zs)
    dmax = max(abs(z_lo), abs(z_hi))
    k = 1 if a.name == "identity" else c
    dG = math.sqrt(k) * fmax * fmax * R2
    dm = math.sqrt(c) * fmax * fmax * dmax * R
    return math.sqrt(dG * dG + dm * dm)


# ----------------------------------------------------------- calibration
def _phi(x: float) -> float:
    return 0.5 * (1.0 + math.erf(x / math.sqrt(2.0)))


def gaussian_delta(eps: float, sens: float, sigma: float) -> float:
    """Exact δ of the Gaussian mechanism at scale σ (Balle & Wang 2018,
    Thm. 8): ``δ = Φ(Δ/2σ − εσ/Δ) − e^ε Φ(−Δ/2σ − εσ/Δ)``.

    The second term is evaluated in log space: a bare ``exp(ε)``
    overflows for ε > ~709 even though the product is finite (Φ of a
    very negative argument underflows first), and large-ε sweeps are
    legal inputs.
    """
    if sigma <= 0:
        return 1.0
    r = sens / sigma
    first = _phi(r / 2 - eps / r)
    phi_b = _phi(-r / 2 - eps / r)
    if phi_b == 0.0:
        return first
    log_term = eps + math.log(phi_b)
    return first - (math.exp(log_term) if log_term < 700.0
                    else math.inf)


def calibrate_sigma(eps: float, delta: float, sens: float) -> float:
    """Smallest σ making one Gaussian release (ε, δ)-DP (bisection on
    the exact condition — valid at every ε, tight to ~1e-6 relative)."""
    validate_budget(eps, delta)
    if sens < 0:
        raise ValueError(f"sensitivity must be >= 0, got {sens}")
    if math.isinf(eps) or sens == 0:
        return 0.0
    lo, hi = 1e-12 * sens, sens
    while gaussian_delta(eps, sens, hi) > delta:
        hi *= 2.0
        if hi > 1e12 * sens:        # unreachable for valid (ε, δ)
            raise ValueError(
                f"cannot calibrate sigma for eps={eps}, delta={delta}")
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if gaussian_delta(eps, sens, mid) > delta:
            lo = mid
        else:
            hi = mid
    return hi


def validate_budget(eps: float, delta: float) -> None:
    """Reject invalid ``(ε, δ)`` loudly (satellite: accountant must)."""
    if not isinstance(eps, (int, float)) or math.isnan(eps) or eps <= 0:
        raise ValueError(f"epsilon must be > 0 (or inf), got {eps!r}")
    if not isinstance(delta, (int, float)) or math.isnan(delta) \
            or not 0.0 <= delta < 1.0:
        raise ValueError(f"delta must be in [0, 1), got {delta!r}")
    if delta == 0.0 and not math.isinf(eps):
        raise ValueError(
            "delta=0 needs eps=inf: a Gaussian release is never "
            "(eps, 0)-DP")


@dataclasses.dataclass
class DPAccountant:
    """Running ``(ε, δ)`` ledger under basic composition.

    The paper's one-round method makes this trivial — the intended
    lifetime is a single :meth:`spend`. Extra releases (a late-join
    ``W_first``, extra ledger ticks) compose additively and are visible
    in ``spent``; nothing is hidden behind an amplification argument.
    A clip-only (ε=∞) release records ``eps_spent = inf`` — an
    unnoised release provides NO differential privacy, and reporting
    it as ε=0 (the strongest possible claim) would be the exact
    inversion of the truth.
    """
    eps_spent: float = 0.0
    delta_spent: float = 0.0
    releases: int = 0

    def spend(self, eps: float, delta: float) -> None:
        validate_budget(eps, delta)
        self.eps_spent += eps           # inf stays inf — honest
        self.delta_spent += delta
        self.releases += 1

    @property
    def spent(self) -> Tuple[float, float]:
        return self.eps_spent, self.delta_spent


# -------------------------------------------------------------- noising
def noise_stats(stats: GramStats, sigma: float, key) -> GramStats:
    """One Gaussian perturbation of ``(G, m_vec)``; ``n`` untouched.

    Gram blocks get *symmetric* noise (upper triangle drawn iid,
    mirrored — AnalyzeGauss) so the perturbed Gram stays symmetric and
    the ridge solve well-posed; ``m_vec`` gets iid noise.
    """
    if sigma < 0:
        raise ValueError(f"sigma must be >= 0, got {sigma}")
    if sigma == 0:
        return stats
    G = jnp.asarray(stats.G)
    kG, kM = jax.random.split(jax.random.fold_in(key, 0))
    Z = jax.random.normal(kG, G.shape, G.dtype) * sigma
    iu = jnp.triu(jnp.ones(G.shape[-2:], bool))
    Zs = jnp.where(iu, Z, jnp.swapaxes(Z, -1, -2))
    M = jax.random.normal(kM, stats.m_vec.shape,
                          stats.m_vec.dtype) * sigma
    return GramStats(G=G + Zs, m_vec=stats.m_vec + M, n=stats.n)


def noise_factor_stats(stats: ClientStats, sigma: float,
                       key) -> ClientStats:
    """One Gaussian perturbation of the svd wire's ``(U·S, m_vec)``.

    The singular factors are not an additive release, but the model
    they determine only depends on them through the Gram image
    ``G = (U·S)(U·S)ᵀ`` (the solve's gain is a function of ``s²`` and
    ``U`` — DESIGN.md §2), and *that* is a sum over samples with the
    same joint ``(G, m_vec)`` sensitivity bound as the gram wire
    (:func:`sensitivity`). So noise enters on the Gram image —
    symmetric, AnalyzeGauss-style, exactly as :func:`noise_stats` —
    and the factors are rebuilt by eigendecomposition with negative
    eigenvalues clamped (the PSD projection is built in; rebuilding
    factors from the released noisy Gram is post-processing and costs
    no extra privacy). ``n`` is released exactly, as on the gram path.

    σ = 0 returns the statistics untouched, keeping the ε=∞ clip-only
    path bit-identical (the eigh round-trip is not bit-neutral).
    """
    if sigma < 0:
        raise ValueError(f"sigma must be >= 0, got {sigma}")
    if sigma == 0:
        return stats
    A = jnp.asarray(stats.US)                       # (k, m, r)
    G = A @ jnp.swapaxes(A, -1, -2)
    kG, kM = jax.random.split(jax.random.fold_in(key, 0))
    Z = jax.random.normal(kG, G.shape, G.dtype) * sigma
    iu = jnp.triu(jnp.ones(G.shape[-2:], bool))
    Zs = jnp.where(iu, Z, jnp.swapaxes(Z, -1, -2))
    M = jax.random.normal(kM, stats.m_vec.shape,
                          stats.m_vec.dtype) * sigma
    w, V = jnp.linalg.eigh(G + Zs)
    w = jnp.maximum(w, 0.0)
    # eigh orders ascending; the wire's factors follow SVD convention
    # (descending), and the solve's gain 1/(s²+λ) is order-coupled to
    # the columns of U, so flip both together
    return ClientStats(U=V[..., ::-1], s=jnp.sqrt(w[..., ::-1]),
                       m_vec=stats.m_vec + M, n=stats.n)


def psd_project(stats: GramStats) -> GramStats:
    """Clamp each noised Gram block back onto the PSD cone.

    Gaussian noise of any useful scale makes ``G + λI`` indefinite for
    small λ, and the coordinator's Cholesky then emits NaN. Projecting
    (eigendecompose, zero the negative eigenvalues — the AnalyzeGauss
    post-processing) restores SPD-ness; as pure post-processing of the
    released statistics it costs no privacy. Only call when σ > 0: the
    eigh round-trip is not bit-neutral, and the ε=∞ path must stay
    bit-identical to the clipped noiseless baseline.
    """
    G = jnp.asarray(stats.G)
    w, V = jnp.linalg.eigh(G)
    w = jnp.maximum(w, 0.0)
    G_psd = jnp.einsum("...ij,...j,...kj->...ik", V, w, V)
    return GramStats(G=G_psd, m_vec=stats.m_vec, n=stats.n)


def noise_leaves_like(stats, sigma: float, key):
    """Generic fallback for non-Gram additive stats: iid noise on every
    float leaf except the trailing ``n`` counter."""
    if sigma == 0:
        return stats
    leaves, treedef = jax.tree_util.tree_flatten(stats)
    out = []
    for i, lf in enumerate(leaves):
        lf = jnp.asarray(lf)
        if lf.ndim == 0:            # the sample counter: released exact
            out.append(lf)
            continue
        out.append(lf + jax.random.normal(jax.random.fold_in(key, i),
                                          lf.shape, lf.dtype) * sigma)
    return jax.tree_util.tree_unflatten(treedef, out)
