"""Property-based tests for the controller's closed-form math (Eq. 2/3)
and the pinned Φ1 cost-function behavior.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.controller import (CostFunctions, eq2_beta,
                                   eq3_migration_prefix)


def _costs(omega1, omega2, phi1b, phi1s, phi2s):
    return CostFunctions(omega1=omega1, omega2_slope=omega2,
                         phi1_base=phi1b, phi1_slope=phi1s, phi2_slope=phi2s)


# ---------------------------------------------------------------------------
# Φ1: the intended discontinuity at n = 0 (satellite fix, pinned)
# ---------------------------------------------------------------------------


class TestPhi1:
    C = _costs(1e-3, 1e-5, 5e-5, 2e-5, 1e-4)

    def test_zero_columns_cost_nothing(self):
        """Migrating nothing launches no collective: Φ1(0) = 0 exactly."""
        assert self.C.phi1(0.0) == 0.0

    def test_negative_clamped_to_zero(self):
        assert self.C.phi1(-3.0) == 0.0

    def test_first_column_pays_full_launch_latency(self):
        """The jump at 0+ IS the collective launch cost — intended and
        documented; Eq.(3) prices the first migrated column with it."""
        eps = 1e-9
        assert self.C.phi1(eps) == pytest.approx(self.C.phi1_base, rel=1e-6)
        # the discontinuity equals phi1_base
        assert self.C.phi1(eps) - self.C.phi1(0.0) \
            == pytest.approx(self.C.phi1_base, rel=1e-6)

    @given(n=st.floats(0.0, 1e4), m=st.floats(0.0, 1e4))
    @settings(max_examples=50, deadline=None)
    def test_monotone_nondecreasing(self, n, m):
        lo, hi = sorted((n, m))
        assert self.C.phi1(lo) <= self.C.phi1(hi) + 1e-12


# ---------------------------------------------------------------------------
# Eq.(2): β ∈ [0, 1], monotone in the straggler's γ
# ---------------------------------------------------------------------------


class TestEq2Properties:
    @given(lg=st.floats(1e-3, 1e5), e=st.integers(2, 64),
           omega1=st.floats(0, 1e-2), omega2=st.floats(1e-9, 1e-3),
           phi1b=st.floats(0, 1e-2), phi1s=st.floats(1e-9, 1e-3),
           phi2s=st.floats(1e-9, 1e-3))
    @settings(max_examples=100, deadline=None)
    def test_beta_in_unit_interval(self, lg, e, omega1, omega2, phi1b,
                                   phi1s, phi2s):
        b = eq2_beta(lg, _costs(omega1, omega2, phi1b, phi1s, phi2s), e)
        assert 0.0 <= b <= 1.0

    @given(L=st.floats(8, 512), e=st.integers(2, 32),
           omega1=st.floats(0, 1e-2), omega2=st.floats(1e-9, 1e-3),
           phi1b=st.floats(0, 1e-2), phi1s=st.floats(1e-9, 1e-3),
           phi2s=st.floats(1e-9, 1e-3))
    @settings(max_examples=100, deadline=None)
    def test_beta_monotone_in_gamma(self, L, e, omega1, omega2, phi1b,
                                    phi1s, phi2s):
        """β(γ) is monotone, direction fixed by the cost balance:
        dβ/dγ ∝ (Φ1_base − Ω1) before clipping — a larger straggler
        workload tilts toward migration iff the collective launch cost
        dominates the static realloc cost (and clipping to [0,1]
        preserves monotonicity)."""
        costs = _costs(omega1, omega2, phi1b, phi1s, phi2s)
        gammas = np.linspace(0.01, 0.875, 32)
        betas = np.array([eq2_beta(g * L, costs, e) for g in gammas])
        d = np.diff(betas)
        sign = 1.0 if phi1b >= omega1 else -1.0
        assert np.all(sign * d >= -1e-9)


# ---------------------------------------------------------------------------
# Eq.(3): the selected prefix is genuinely cost-effective, and the choice
# depends only on the multiset of rank times
# ---------------------------------------------------------------------------


def _f_of(x, times_desc, workloads, costs, e):
    """Independent recomputation of f(x) from the paper's definition."""
    t_min = float(times_desc.min())
    gamma_x = sum(workloads[k] * (times_desc[k] - t_min) / times_desc[k]
                  for k in range(x) if times_desc[k] > 0)
    recv = max((gamma_x / max(e - x, 1))
               * (times_desc[y] / max(workloads[y], 1e-12))
               for y in range(x, len(times_desc)))
    return (times_desc[x - 1] - t_min) - costs.phi1(gamma_x) - recv


class TestEq3Properties:
    @given(e=st.integers(2, 16), w=st.integers(8, 128),
           phi1b=st.floats(0, 0.5), phi1s=st.floats(0, 0.05),
           seed=st.integers(0, 10_000))
    @settings(max_examples=100, deadline=None)
    def test_prefix_members_have_positive_f(self, e, w, phi1b, phi1s, seed):
        """Every rank inside the returned migration prefix satisfies
        f(k) > 0 (recomputed independently): migration is never selected
        for a rank where it is not cost-effective."""
        rng = np.random.default_rng(seed)
        chis = rng.choice([1.0, 1.0, 2.0, 4.0, 8.0], size=e)
        times = np.sort(chis * rng.uniform(0.9, 1.1, e))[::-1]
        workloads = np.full(e, float(w))
        costs = _costs(0.0, 0.0, phi1b, phi1s, 0.0)
        x = eq3_migration_prefix(times, workloads, costs, e)
        assert 0 <= x < e
        for k in range(1, x + 1):
            assert _f_of(k, times, workloads, costs, e) > 0

    @given(e=st.integers(3, 12), seed=st.integers(0, 10_000),
           phi1b=st.floats(0, 0.3), phi1s=st.floats(0, 0.05))
    @settings(max_examples=100, deadline=None)
    def test_invariant_to_permutation_of_equal_time_ranks(self, e, seed,
                                                          phi1b, phi1s):
        """With equal per-rank workloads the prefix choice depends only on
        the MULTISET of times: permuting ranks (including within tie
        groups — the draw set forces ties) never changes x."""
        rng = np.random.default_rng(seed)
        times = rng.choice([1.0, 1.0, 2.0, 4.0], size=e)  # ties guaranteed
        workloads = np.full(e, 64.0)
        costs = _costs(0.0, 0.0, phi1b, phi1s, 0.0)
        ref = eq3_migration_prefix(np.sort(times)[::-1], workloads, costs, e)
        for _ in range(4):
            perm = rng.permutation(e)
            x = eq3_migration_prefix(np.sort(times[perm])[::-1],
                                     workloads, costs, e)
            assert x == ref
