"""Controller (Eqs. 1-3, Alg. 2) and priority (Alg. 1) tests."""
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import WorkloadControlConfig
from repro.core import hetero as hetero_lib
from repro.core import priority as pri_lib
from repro.core.controller import (CostFunctions, SemiController, eq1_gamma,
                                   eq2_beta, eq3_migration_prefix,
                                   work_fraction)
from repro.core.workload import keep_blocks_for_bucket

from test_multidevice import run_py


COSTS = CostFunctions(omega1=1e-3, omega2_slope=1e-5, phi1_base=5e-5,
                      phi1_slope=2e-5, phi2_slope=1e-4)


class TestEq1:
    def test_no_gap_no_pruning(self):
        assert eq1_gamma(1.0, 1.0, 1.0) == 0.0

    def test_gap_offset(self):
        # 2x slower with matmul share 1.0 of runtime: prune half
        assert eq1_gamma(2.0, 1.0, 2.0) == pytest.approx(0.5)

    @given(t=st.floats(0.1, 10), ref=st.floats(0.1, 10), m=st.floats(0.01, 10))
    @settings(max_examples=50, deadline=None)
    def test_bounds(self, t, ref, m):
        g = eq1_gamma(t, ref, m)
        assert 0.0 <= g <= 0.875


class TestEq2:
    def test_zero_workload(self):
        assert eq2_beta(0.0, COSTS, 8) == 0.0

    @given(lg=st.floats(1.0, 1e4), e=st.integers(2, 64))
    @settings(max_examples=50, deadline=None)
    def test_beta_in_unit_interval(self, lg, e):
        assert 0.0 <= eq2_beta(lg, COSTS, e) <= 1.0

    def test_cheap_migration_prefers_migration(self):
        cheap = CostFunctions(omega1=1.0, omega2_slope=1.0,
                              phi1_base=0.0, phi1_slope=1e-9,
                              phi2_slope=1e-9)
        assert eq2_beta(100.0, cheap, 8) == 1.0

    def test_expensive_migration_prefers_resizing(self):
        dear = CostFunctions(omega1=0.0, omega2_slope=1e-9,
                             phi1_base=10.0, phi1_slope=10.0, phi2_slope=10.0)
        assert eq2_beta(100.0, dear, 8) == 0.0


class TestEq3:
    def test_uniform_times_no_migration(self):
        t = np.ones(8)
        x = eq3_migration_prefix(t, np.full(8, 100.0), COSTS, 8)
        assert x == 0

    def test_single_heavy_straggler_migrates(self):
        t = np.array([8.0, 1, 1, 1, 1, 1, 1, 1])
        x = eq3_migration_prefix(t, np.full(8, 100.0), COSTS, 8)
        assert x >= 1

    def test_prefix_grows_with_cheaper_comm(self):
        t = np.array([8.0, 6, 4, 3, 1, 1, 1, 1])
        w = np.full(8, 100.0)
        cheap = CostFunctions(0, 0, 0, 1e-9, 1e-9)
        dear = CostFunctions(0, 0, 1.0, 1.0, 1.0)
        assert (eq3_migration_prefix(t, w, cheap, 8)
                >= eq3_migration_prefix(t, w, dear, 8))


class TestPriority:
    def test_incremental_update_preserves_pruned_stats(self):
        """The endless-loop fix (Sec. III-B): pruned blocks keep their old
        statistic — zero-imputed non-updates must not look 'unimportant'."""
        st_ = pri_lib.PriorityState.create(4)
        w0 = np.zeros((4 * 8, 3))
        st_ = pri_lib.update_state(st_, w0, 8)
        # big refinement on blocks 0,1; none on 2,3 (they were pruned)
        w1 = w0.copy()
        w1[:16] += 1.0
        st_ = pri_lib.update_state(st_, w1, 8)
        pri = pri_lib.build_pri_list(st_)
        st_ = pri_lib.mark_pruned(st_, pri, keep_blocks=2)   # prune 2 worst
        assert set(np.asarray(st_.pruned_last).nonzero()[0]) == {2, 3}
        var_before = st_.w_var.copy()
        # next epoch: pruned blocks didn't move (zero imputation), others did
        w2 = w1.copy()
        w2[:16] += 1.0
        st_ = pri_lib.update_state(st_, w2, 8)
        # pruned blocks' stats preserved, NOT refreshed to ~0
        np.testing.assert_array_equal(st_.w_var[2:], var_before[2:])

    def test_priority_keeps_high_variation(self):
        st_ = pri_lib.PriorityState.create(3)
        st_.w_var[:] = [0.5, 0.1, 0.9]
        pri = pri_lib.build_pri_list(st_)
        assert list(pri) == [2, 0, 1]

    def test_differentiated_gamma_floor(self):
        """γ_k >= α·γ_uniform (Alg. 1 line 11, bucket-rounded)."""
        states = {"a": pri_lib.PriorityState.create(8)}
        states["a"].w_var[:] = 1.0      # everything still moving -> γ_k = 0
        buckets = (0.0, 0.25, 0.5, 0.75)
        out = pri_lib.differentiated_gamma(states, 0.5, alpha=0.8,
                                           theta=1e-3, buckets=buckets)
        assert buckets[out["a"]] >= 0.8 * 0.5 - 1e-9


def stat_fixture(shapes, block, seed, refreshes=3):
    """Layer-stacked weights [L, K, N] per scope for each refresh.

    Every value is a multiple of 2**-14 well inside float32's mantissa,
    and ``L`` is a power of two, so the mean over layers is exact in any
    summation order (XLA divides by ``L`` as a product with its rounded
    reciprocal) and the device and numpy snapshots can be compared bit
    for bit. Each block moves by
    its own step (1/64 apart) plus small per-element noise, so no two
    blocks' statistics lie within 1e-5 of each other."""
    rng = np.random.default_rng(seed)
    out = [{} for _ in range(refreshes)]
    for name, (L, K, N) in shapes.items():
        nb = K // block
        w = rng.integers(-512, 513, (L, K, N)) / 1024
        step = np.repeat(rng.permutation(nb) + 1.0, block)[:, None] / 64
        for r in range(refreshes):
            sign = rng.choice([-1.0, 1.0], (K, N))
            w = w + step * sign + rng.integers(-4, 5, (L, K, N)) / 4096
            out[r][name] = w.astype(np.float32)
    return out


def check_device_refresh(shapes, shardings, block, seed=0):
    """Three refreshes through ``WeightStats`` and through the numpy
    ``update_state``, with half of every scope's blocks pruned after
    each: the statistics agree to 1e-5, the keep-first orders are the
    same and the snapshots are bit-identical, on the rows each path
    keeps and on those it advances."""
    import jax
    from repro.launch.train import WeightStats
    steps = stat_fixture(shapes, block, seed)
    put = lambda ws: {k: jax.device_put(w, shardings[k])
                      for k, w in ws.items()}
    stats = WeightStats(put(steps[0]), block)
    dev, ref = {}, {}
    kept_rows = 0
    for ws in steps:
        fresh, snaps = stats(put(ws), dev)
        for k, w in ws.items():
            nb = w.shape[1] // block
            st = dev.get(k) or pri_lib.PriorityState.create(nb)
            kept_rows += int(st.pruned_last.sum()) * block
            dev[k] = pri_lib.apply_variation(st, fresh[k], snaps[k])
            ref[k] = pri_lib.update_state(
                ref.get(k) or pri_lib.PriorityState.create(nb),
                w.mean(axis=0), block)
            np.testing.assert_allclose(dev[k].w_var, ref[k].w_var,
                                       rtol=1e-5)
            np.testing.assert_array_equal(np.asarray(dev[k].snapshot),
                                          ref[k].snapshot)
            finite = np.sort(ref[k].w_var[np.isfinite(ref[k].w_var)])
            assert np.all(np.diff(finite) > 1e-5 * finite[1:])
            pri = pri_lib.build_pri_list(ref[k])
            np.testing.assert_array_equal(pri_lib.build_pri_list(dev[k]),
                                          pri)
            dev[k] = pri_lib.mark_pruned(dev[k], pri, nb // 2)
            ref[k] = pri_lib.mark_pruned(ref[k], pri, nb // 2)
    assert kept_rows > 0


@pytest.mark.parametrize("block", [8, 128])
def test_device_refresh_matches_update_state(block):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_small_mesh
    shapes = {"ffn": (4, 4 * block, 24), "qkv": (4, 2 * block, 24),
              "attn_out": (4, 2 * block, 16)}
    one = NamedSharding(make_small_mesh(1, 1), P())
    check_device_refresh(shapes, {k: one for k in shapes}, block)


def test_device_refresh_matches_update_state_tp4():
    """The same at four host devices, on the trainer's shardings."""
    run_py(f"""
import sys
sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
import jax, jax.numpy as jnp
from repro.config import ShapeConfig, TrainConfig, resolve_model
from repro.launch import steps
from repro.launch.mesh import make_small_mesh
from repro.launch.train import stat_weights
from repro.models import get_api
from repro.sharding import use_mesh
from test_controller import check_device_refresh
cfg = resolve_model("vit-1b")
mesh = make_small_mesh(1, 4)
with use_mesh(mesh):
    _, _, in_sh, _ = steps.build_train_step(
        cfg, ShapeConfig("trainer", 64, 4, "train"), mesh, TrainConfig())
    scopes = ("ffn", "qkv", "attn_out")
    shapes = stat_weights(jax.eval_shape(lambda: get_api(cfg).init(
        jax.random.PRNGKey(0), cfg, jnp.float32)[0]), scopes)
    shardings = stat_weights(in_sh[0], scopes)
    assert set(shapes) == set(scopes)
    assert any("model" in str(s.spec) for s in shardings.values())
    check_device_refresh({{k: v.shape for k, v in shapes.items()}},
                         shardings, 8)
print("ok")
""", devices=4)


def test_resume_after_device_refresh():
    """A controller restored from ``state_arrays`` taken after a device
    refresh measures the next refresh as the uninterrupted one does."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_small_mesh
    from repro.launch.train import WeightStats
    block = 8
    one = NamedSharding(make_small_mesh(1, 1), P())
    steps = [{k: jax.device_put(w, one) for k, w in ws.items()}
             for ws in stat_fixture({"ffn": (4, 64, 24), "qkv": (4, 32, 24)},
                                    block, seed=1)]
    stats = WeightStats(steps[0], block)
    cfg = WorkloadControlConfig(enabled=True, mode="zero", block_size=block)
    model = hetero_lib.IterationModel(matmul_time=1.0, other_time=0.1)

    def controller():
        return SemiController(cfg, 4, model, num_blocks=8)

    def refresh(c, ws):
        c.observe_variation(*stats(ws, c.priority))

    times = np.array([2.0, 1.0, 1.0, 1.0])
    a = controller()
    for ws in steps[:2]:
        refresh(a, ws)
        a.plan(times)
    assert any(st.pruned_last.any() for st in a.priority.values())
    b = controller()
    b.load_state_arrays(a.state_arrays())
    assert all(isinstance(st.snapshot, np.ndarray)
               for st in b.priority.values())
    refresh(a, steps[2])
    refresh(b, steps[2])
    for k in a.priority:
        np.testing.assert_array_equal(b.priority[k].w_var,
                                      a.priority[k].w_var)
        assert np.isfinite(a.priority[k].w_var).all()
    want = a.pri_lists()
    got = b.pri_lists()
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


class TestSemiController:
    def _mk(self, mode="semi", tp=8):
        cfg = WorkloadControlConfig(enabled=True, mode=mode, block_size=8)
        model = hetero_lib.IterationModel(matmul_time=1.0, other_time=0.1)
        return SemiController(cfg, tp, model, num_blocks=64)

    def test_no_stragglers_neutral(self):
        c = self._mk()
        plan, rep = c.plan(np.ones(8))
        assert plan.is_neutral()
        assert rep.stragglers == []

    def test_zero_mode_buckets_straggler(self):
        c = self._mk("zero")
        times = np.ones(8)
        times[3] = 2.0
        plan, rep = c.plan(times)
        assert plan.dynamic.bucket_by_rank[3] > 0
        assert all(plan.dynamic.bucket_by_rank[i] == 0 for i in range(8) if i != 3)

    def test_semi_single_straggler_splits(self):
        c = self._mk("semi")
        times = np.ones(8)
        times[0] = 3.0
        plan, rep = c.plan(times)
        assert rep.stragglers == [0]
        assert 0.0 <= rep.beta <= 1.0
        # the straggler either migrates, resizes, or both
        assert rep.mig_blocks > 0 or plan.dynamic.bucket_by_rank[0] > 0

    def test_semi_multi_straggler_grouping(self):
        c = self._mk("semi")
        times = np.array([8.0, 6, 4, 2, 1, 1, 1, 1], float)
        plan, rep = c.plan(times)
        assert len(rep.stragglers) == 4
        # heaviest rank migrates (if cost-effective) or resizes hardest
        assert rep.mig_src in (-1, 0)

    def test_work_fraction_balances(self):
        """After planning, the modeled per-rank times should be closer to
        uniform than before (the whole point of Eq. 1)."""
        c = self._mk("zero")
        model = c.model
        chi = np.ones(8)
        chi[2] = 3.0
        times0 = model.times(chi, np.ones(8))
        plan, _ = c.plan(times0)
        frac = work_fraction(plan, c.num_blocks)
        times1 = model.times(chi, frac)
        assert times1.max() / times1.min() < times0.max() / times0.min()


class TestHetero:
    def test_round_robin_single_straggler(self):
        s = hetero_lib.HeteroSchedule(num_ranks=4, kind="round_robin",
                                      chis=(3.0,), period=5)
        for step in range(20):
            chi = s.chi(step)
            assert (chi > 1).sum() == 1
        assert np.argmax(s.chi(0)) != np.argmax(s.chi(5))

    def test_static(self):
        s = hetero_lib.HeteroSchedule(num_ranks=4, kind="static",
                                      chis=(2.0, 1.0, 1.0, 1.0))
        np.testing.assert_array_equal(s.chi(0), [2, 1, 1, 1])

    def test_iteration_model_step_time_is_max(self):
        m = hetero_lib.IterationModel(matmul_time=1.0, other_time=0.0)
        chi = np.array([1.0, 4.0])
        assert m.step_time(chi, np.ones(2)) == pytest.approx(4.0)
        # pruning the straggler to 1/4 work restores balance
        assert m.step_time(chi, np.array([1.0, 0.25])) == pytest.approx(1.0)
