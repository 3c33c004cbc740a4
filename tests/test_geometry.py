"""First-class ragged shard geometry (core/geometry.py, DESIGN_SHARDING.md).

Property tests for the geometry object and its χ-seeding, the padded
param expansion, the plan-layer composition (PlanStatic signatures,
per-rank priority rows, residual controller planning) and — in
subprocesses with forced host devices — the numerical contracts:

* an all-EQUAL geometry is normalized away and bit-matches the
  geometry-free equal-shard baseline (forward AND grads);
* any valid UNEVEN geometry (including a min-slice rank) matches the
  canonical dense oracle to float tolerance, neutral / resized /
  migrated alike, with migration lossless in forward and backward;
* serve decode under an uneven geometry + the lossless β-policy is
  token-exact vs the same-geometry dense engine.
"""
import warnings

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import geometry as geom
from repro.core.geometry import (ShardGeometry, equal_geometry,
                                 geometry_from_chi, parse_geometry_arg)
from repro.core.workload import PlanStatic

from test_multidevice import run_py


# ---------------------------------------------------------------------------
# the geometry object
# ---------------------------------------------------------------------------


class TestShardGeometry:
    def test_basic_invariants(self):
        g = ShardGeometry(sizes=(4, 10, 9, 9), block=8)
        assert g.tp == 4
        assert g.total_blocks == 32
        assert g.max_blocks == 10 and g.min_blocks == 4
        assert g.offsets == (0, 4, 14, 23)
        assert g.width == 256
        assert g.padded_blocks == 40 and g.padded_width == 320
        assert not g.is_equal
        assert equal_geometry(32, 4, 8).is_equal

    def test_rank_of_block_partitions(self):
        g = ShardGeometry(sizes=(2, 14, 8, 8), block=8)
        owners = [g.rank_of_block(b) for b in range(g.total_blocks)]
        for r in range(g.tp):
            assert owners.count(r) == g.sizes[r]
        assert owners == sorted(owners)          # contiguous canonical spans

    def test_rejects_empty_rank(self):
        with pytest.raises(ValueError):
            ShardGeometry(sizes=(0, 16, 8, 8), block=8)

    @given(tp=st.sampled_from([1, 2, 4]),
           data=st.data())
    @settings(max_examples=40, deadline=None)
    def test_random_partition_invariants(self, tp, data):
        """Any random uneven partition (min-slice ranks included) keeps
        the layout algebra consistent."""
        total = data.draw(st.integers(tp, 48))
        cuts = sorted(data.draw(
            st.lists(st.integers(1, max(total - 1, 1)), min_size=tp - 1,
                     max_size=tp - 1)))
        sizes, prev = [], 0
        for c in cuts + [total]:
            sizes.append(max(c - prev, 1))
            prev = c
        # repair: force the sum back to total (draws may collide)
        sizes[-1] += total - sum(sizes)
        if sizes[-1] < 1:
            return
        g = ShardGeometry(sizes=tuple(sizes), block=8)
        assert sum(g.sizes) == g.total_blocks == total
        assert g.offsets[0] == 0
        assert all(g.offsets[r + 1] - g.offsets[r] == g.sizes[r]
                   for r in range(tp - 1))
        assert g.padded_blocks == tp * max(sizes)
        assert g.padded_width % tp == 0


class TestGeometryFromChi:
    def test_two_x_straggler_gets_half_share(self):
        g = geometry_from_chi([2.0, 1.0, 1.0, 1.0], 32, 8)
        assert g.sizes == (5, 9, 9, 9)
        assert sum(g.sizes) == 32

    @given(tp=st.sampled_from([2, 4]), data=st.data())
    @settings(max_examples=40, deadline=None)
    def test_sum_min_and_monotonicity(self, tp, data):
        chis = [data.draw(st.floats(0.5, 8.0)) for _ in range(tp)]
        total = data.draw(st.integers(tp, 64))
        g = geometry_from_chi(chis, total, 8)
        assert sum(g.sizes) == total
        assert min(g.sizes) >= 1
        # a strictly slower rank never gets MORE blocks (after the χ snap)
        q = [max(round(c / 0.25) * 0.25, 1.0) for c in chis]
        for i in range(tp):
            for j in range(tp):
                if q[i] > q[j]:
                    assert g.sizes[i] <= g.sizes[j]

    def test_uniform_chi_is_equal(self):
        assert geometry_from_chi([3.0] * 4, 32, 8).is_equal


class TestParseArg:
    def test_none_forms(self):
        assert parse_geometry_arg(None, 4) is None
        assert parse_geometry_arg("", 4) is None
        assert parse_geometry_arg("none", 4) is None

    def test_explicit_counts(self):
        assert parse_geometry_arg("12,12,4,4", 4) == (12, 12, 4, 4)

    def test_wrong_rank_count(self):
        with pytest.raises(ValueError):
            parse_geometry_arg("12,20", 4)


# ---------------------------------------------------------------------------
# padded param expansion
# ---------------------------------------------------------------------------


class TestParamExpansion:
    def _params(self, d=6, width=256, layers=2):
        rng = np.random.default_rng(7)
        return {"stack": {"scan": {"ffn": {
            "w_up": rng.standard_normal((layers, d, width)),
            "w_gate": rng.standard_normal((layers, d, width)),
            "w_down": rng.standard_normal((layers, width, d)),
        }}}}

    @given(data=st.data())
    @settings(max_examples=20, deadline=None)
    def test_roundtrip_exact(self, data):
        tp = data.draw(st.sampled_from([2, 4]))
        chis = [data.draw(st.floats(1.0, 4.0)) for _ in range(tp)]
        g = geometry_from_chi(chis, 32, 8)
        p = self._params()
        q = geom.restrict_ffn_params(geom.expand_ffn_params(p, g), g)
        for k in ("w_up", "w_gate", "w_down"):
            np.testing.assert_array_equal(
                q["stack"]["scan"]["ffn"][k], p["stack"]["scan"]["ffn"][k])

    def test_padding_is_zero_and_real_blocks_land_in_rank_slices(self):
        g = ShardGeometry(sizes=(2, 14, 8, 8), block=8)
        p = self._params()
        e = geom.expand_ffn_params(p, g)["stack"]["scan"]["ffn"]
        wu = e["w_up"]
        assert wu.shape[-1] == g.padded_width
        loc = g.max_blocks * g.block
        for r, (L, off) in enumerate(zip(g.sizes, g.offsets)):
            sl = wu[..., r * loc:(r + 1) * loc]
            np.testing.assert_array_equal(
                sl[..., :L * g.block],
                p["stack"]["scan"]["ffn"]["w_up"][
                    ..., off * g.block:(off + L) * g.block])
            assert not sl[..., L * g.block:].any()
        wd = e["w_down"]
        assert not wd[:, 2 * g.block:loc, :].any()   # rank 0 pad rows zero

    def test_no_ffn_pair_raises(self):
        with pytest.raises(ValueError):
            geom.expand_ffn_params({"w": np.zeros((4, 4))},
                                   ShardGeometry(sizes=(1, 3), block=8))


# ---------------------------------------------------------------------------
# plan-layer composition
# ---------------------------------------------------------------------------


class TestPlanStaticGeometry:
    def test_equal_geometry_normalizes_to_baseline_signature(self):
        base = PlanStatic(tp_size=4, block_size=8)
        geo = PlanStatic(tp_size=4, block_size=8, geometry=(8, 8, 8, 8))
        assert geo.canonical().geometry == ()
        assert geo.signature_str() == base.signature_str()

    def test_uneven_geometry_tags_signature(self):
        a = PlanStatic(tp_size=4, block_size=8, geometry=(10, 10, 6, 6))
        b = PlanStatic(tp_size=4, block_size=8, geometry=(6, 6, 10, 10))
        assert "geo[10,10,6,6]" in a.signature_str()
        assert a.signature_str() != b.signature_str()

    def test_geometry_rank_mismatch_raises(self):
        with pytest.raises(ValueError):
            PlanStatic(tp_size=4, block_size=8, geometry=(10, 22))


class TestPerRankPriGeometry:
    def test_identity_rows_real_then_padding(self):
        from repro.control.scopes import per_rank_pri
        sizes = (4, 10, 9, 9)
        rows = per_rank_pri(np.arange(32), 4, 10, geometry=sizes)
        for r, L in enumerate(sizes):
            assert list(rows[r][:L]) == list(range(L))      # real, keep-first
            assert list(rows[r][L:]) == list(range(L, 10))  # padding last

    def test_missing_block_raises(self):
        from repro.control.scopes import per_rank_pri
        with pytest.raises(ValueError):
            per_rank_pri(np.arange(31), 4, 10, geometry=(4, 10, 9, 9))


class TestResidualController:
    """χ-seeded static geometry absorbs a persistent straggler: the
    controller, planning RELATIVE to the geometry, sees no residual."""

    def _controller(self, workloads):
        from repro.config import WorkloadControlConfig
        from repro.core.controller import SemiController
        from repro.core.hetero import IterationModel
        wc = WorkloadControlConfig(enabled=True, mode="semi", block_size=8,
                                   max_migration_sources=3)
        model = IterationModel(matmul_time=1.0, other_time=0.1)
        return SemiController(wc, len(workloads), model,
                              int(round(float(np.mean(workloads)))),
                              workloads=np.asarray(workloads, np.float64))

    def test_absorbed_straggler_plans_nothing(self):
        chis = np.array([2.0, 1.0, 1.0, 1.0])
        g = geometry_from_chi(chis, 32, 8)          # (5, 9, 9, 9)
        ctl = self._controller(g.sizes)
        base = np.asarray(g.sizes) / np.mean(g.sizes)
        times = 1.0 * base * chis + 0.1             # residual-only view
        plan, report = ctl.plan(times)
        assert not report.stragglers
        assert plan.static.mig_sheds == ()
        assert int(plan.dynamic.bucket_by_rank.max()) == 0

    def test_unabsorbed_residual_still_mitigated(self):
        # geometry sized for χ=2 but the rank actually runs at χ=4:
        # the residual (≈2×) must still be detected and mitigated
        g = geometry_from_chi([2.0, 1.0, 1.0, 1.0], 32, 8)
        ctl = self._controller(g.sizes)
        chis = np.array([4.0, 1.0, 1.0, 1.0])
        base = np.asarray(g.sizes) / np.mean(g.sizes)
        plan, report = ctl.plan(1.0 * base * chis + 0.1)
        assert 0 in report.stragglers
        assert plan.static.geometry == g.sizes
        # sheds stay inside the smallest rank's real blocks
        assert all(m < min(g.sizes) for m in plan.static.mig_sheds)


# ---------------------------------------------------------------------------
# numerical contracts (subprocess, forced host devices)
# ---------------------------------------------------------------------------

GEO_PREAMBLE = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.layers.tp_linear import ControlContext, controlled_ffn
from repro.core.workload import PlanStatic
from repro.core.geometry import ShardGeometry
from repro.control.scopes import per_rank_pri
from repro.core import geometry as geom

e, B, S, d, block = 4, 2, 8, 16, 8
geo = ShardGeometry(sizes=GEO_SIZES, block=block)
H = geo.width                       # canonical FFN width
Hp = geo.padded_width
nb_loc = geo.max_blocks
mesh = Mesh(np.array(jax.devices()[:e]).reshape(1, e), ("data", "model"))
rng = np.random.default_rng(0)
x = jnp.array(rng.standard_normal((B, S, d)), jnp.float32)
wg = jnp.array(rng.standard_normal((d, H))*.1, jnp.float32)
wu = jnp.array(rng.standard_normal((d, H))*.1, jnp.float32)
wd = jnp.array(rng.standard_normal((H, d))*.1, jnp.float32)
act = jax.nn.silu
ref = (act(x @ wg) * (x @ wu)) @ wd
pp = geom.expand_ffn_params(
    {"w_up": np.asarray(wu), "w_gate": np.asarray(wg),
     "w_down": np.asarray(wd)}, geo)
wup, wgp, wdp = (jnp.asarray(pp["w_up"]), jnp.asarray(pp["w_gate"]),
                 jnp.asarray(pp["w_down"]))
buckets = (0.0, 0.25, 0.5)

def make_ctx(m, bucket_vec, src, sizes=None):
    st = PlanStatic(buckets=buckets, block_size=block, mig_blocks=m,
                    tp_size=e, geometry=sizes or ())
    pri = jnp.asarray(per_rank_pri(np.arange(e * nb_loc), e, nb_loc,
                                   geometry=sizes))
    return ControlContext(mesh=mesh, axis="model", static=st,
        bucket_by_rank=jnp.array(bucket_vec, jnp.int32),
        mig_src=jnp.array(src, jnp.int32), pri={"ffn": pri})
"""


def geo_py(sizes, body):
    return GEO_PREAMBLE.replace("GEO_SIZES", repr(tuple(sizes))) + body


class TestEqualGeometryBitMatch:
    def test_forward_and_grads_bit_identical(self):
        """geometry=(L,L,L,L) must trace the SAME program as no geometry:
        outputs and grads are bit-equal, not just close."""
        run_py(geo_py((8, 8, 8, 8), """
assert Hp == H
ctx_eq = make_ctx(2, [0, 2, 0, 0], 1, sizes=(8, 8, 8, 8))
ctx_no = make_ctx(2, [0, 2, 0, 0], 1, sizes=None)
def loss(ctx, wu_, wd_, wg_):
    return jnp.sum(controlled_ffn(x, wu_, wd_, ctx, "ffn", act,
                                  w_gate=wg_)**2)
for ctx in (ctx_eq, ctx_no):
    assert ctx.static.canonical().geometry == ()
y_eq = controlled_ffn(x, wu, wd, ctx_eq, "ffn", act, w_gate=wg)
y_no = controlled_ffn(x, wu, wd, ctx_no, "ffn", act, w_gate=wg)
assert np.array_equal(np.asarray(y_eq), np.asarray(y_no))
g_eq = jax.grad(loss, (1, 2, 3))(ctx_eq, wu, wd, wg)
g_no = jax.grad(loss, (1, 2, 3))(ctx_no, wu, wd, wg)
for a, b in zip(g_eq, g_no):
    assert np.array_equal(np.asarray(a), np.asarray(b))
print("ok")
"""), devices=4)


class TestUnevenGeometryOracle:
    SIZES = (2, 6, 4, 4)          # min-slice rank 0, canonical H = 128

    def test_neutral_matches_dense_oracle(self):
        run_py(geo_py(self.SIZES, """
ctx = make_ctx(0, [0]*e, -1, sizes=geo.sizes)
y = controlled_ffn(x, wup, wdp, ctx, "ffn", act, w_gate=wgp)
assert np.allclose(y, ref, atol=1e-4), np.abs(np.array(y)-ref).max()
print("ok")
"""), devices=4)

    def test_resize_matches_masked_oracle_in_canonical_space(self):
        run_py(geo_py(self.SIZES, """
# rank 1 (6 real blocks) resizes at gamma=0.5: keep count comes from
# the SAME helper the branch tables use, sized to ITS real blocks
from repro.core.workload import keep_blocks_for_bucket
ctx = make_ctx(0, [0, 2, 0, 0], -1, sizes=geo.sizes)
y = controlled_ffn(x, wup, wdp, ctx, "ffn", act, w_gate=wgp)
kc = keep_blocks_for_bucket(0.5, geo.sizes[1])
mask = np.ones(geo.total_blocks, bool)
mask[geo.offsets[1] + kc:geo.offsets[1] + geo.sizes[1]] = False
ref_p = ((act(x @ wg) * (x @ wu)) * np.repeat(mask, block)) @ wd
assert np.allclose(y, ref_p, atol=1e-4), np.abs(np.array(y)-ref_p).max()
print("ok")
"""), devices=4)

    def test_migration_lossless_fwd_and_bwd(self):
        """Migration from the min-slice rank (1 of its 2 real blocks)
        changes nothing: forward and canonical-space grads match dense."""
        run_py(geo_py(self.SIZES, """
ctx = make_ctx(1, [0]*e, 0, sizes=geo.sizes)
y = controlled_ffn(x, wup, wdp, ctx, "ffn", act, w_gate=wgp)
assert np.allclose(y, ref, atol=1e-4)
def loss(wu_, wd_, wg_):
    return jnp.sum(controlled_ffn(x, wu_, wd_, ctx, "ffn", act,
                                  w_gate=wg_)**2)
gu, gdn, gg = jax.grad(loss, (0, 1, 2))(wup, wdp, wgp)
canon = geom.restrict_ffn_params(
    {"w_up": np.asarray(gu), "w_gate": np.asarray(gg),
     "w_down": np.asarray(gdn)}, geo)
gr = jax.grad(lambda wu_, wd_, wg_: jnp.sum(
    (((act(x@wg_))*(x@wu_))@wd_)**2), (0, 1, 2))(wu, wd, wg)
for a, b in ((canon["w_up"], gr[0]), (canon["w_down"], gr[1]),
             (canon["w_gate"], gr[2])):
    assert np.allclose(a, np.asarray(b), atol=1e-3), \
        np.abs(np.asarray(a) - np.asarray(b)).max()
print("ok")
"""), devices=4)


class TestServeTokenExact:
    def test_uneven_geometry_lossless_semi_is_token_exact(self):
        """Serve decode under an uneven geometry + lossless β-policy
        emits the SAME tokens as the same-geometry dense engine."""
        run_py("""
import numpy as np
from repro.control import ControlConfig
from repro.launch.serve import Request, ServeEngine

def run(mode):
    cc = ControlConfig(mode=mode, hetero_kind="static", chi=3.0,
                       geometry=(40, 24))
    eng = ServeEngine("yi-6b", num_slots=2, max_len=10, tp=2, control=cc)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, eng.cfg.vocab_size,
                                        (4,)).astype(np.int32),
                    max_new_tokens=5, arrival_step=i * 2)
            for i in range(3)]
    comps = eng.run(reqs)
    eng.close()
    return {c.uid: c.tokens.tolist() for c in comps}

assert run("off") == run("semi")
print("ok")
""", devices=2)


# ---------------------------------------------------------------------------
# config collapse + deprecation shims (satellites)
# ---------------------------------------------------------------------------


class TestControlConfigShims:
    def test_to_workload_matches_legacy_serve_mapping(self):
        from repro.config import WorkloadControlConfig
        from repro.control import ControlConfig
        c = ControlConfig(mode="semi", block_size=8, max_sources=2,
                          beta_policy="lossless", use_kernel=True,
                          times="measured")
        legacy = WorkloadControlConfig(
            enabled=True, mode="semi", block_size=8,
            max_migration_sources=2, beta_policy="lossless",
            use_kernel=True, times="measured")
        assert c.to_workload() == legacy

    def test_to_workload_trainer_overrides(self):
        from repro.control import ControlConfig
        wc = ControlConfig(mode="off", beta_policy="eq2",
                           shed_cap=2).to_workload(
            enabled=True, migration_sources=0)
        assert wc.enabled and wc.mode == "zero"
        assert wc.max_migration_sources == 0
        assert wc.migration_shed_cap == 2

    def test_serve_control_config_warns(self):
        from repro.launch.serve import ServeControlConfig
        with pytest.warns(DeprecationWarning, match="ControlConfig"):
            c = ServeControlConfig(mode="zero")
        assert c.mode == "zero"

    def test_bad_mode_rejected(self):
        from repro.control import ControlConfig
        with pytest.raises(ValueError):
            ControlConfig(mode="resize")


class TestStepsAliasShim:
    def test_deprecated_reexports_warn_and_resolve(self):
        import importlib
        steps = importlib.import_module("repro.launch.steps")
        from repro.control import scopes as scopes_lib
        with pytest.warns(DeprecationWarning, match="repro.control.scopes"):
            fn = steps.per_rank_pri
        assert fn is scopes_lib.per_rank_pri
        with pytest.warns(DeprecationWarning):
            assert steps.SCOPE_LAYOUT is scopes_lib.SCOPE_LAYOUT

    def test_unknown_attribute_still_raises(self):
        from repro.launch import steps
        with pytest.raises(AttributeError):
            steps.definitely_not_here


class TestInterpretCache:
    def test_cached_resolution_and_reset(self):
        import os
        from repro.kernels import ops
        old = os.environ.get("REPRO_PALLAS_INTERPRET")
        try:
            ops.reset_interpret_cache()
            os.environ["REPRO_PALLAS_INTERPRET"] = "1"
            ops.reset_interpret_cache()
            assert ops.interpret_mode() is True
            # cached: flipping the env WITHOUT reset does not change it
            os.environ["REPRO_PALLAS_INTERPRET"] = "0"
            assert ops.interpret_mode() is True
            ops.reset_interpret_cache()
            assert ops.interpret_mode() is False
            # the live module override still wins over the cache
            ops.INTERPRET = True
            assert ops.interpret_mode() is True
        finally:
            ops.INTERPRET = None
            if old is None:
                os.environ.pop("REPRO_PALLAS_INTERPRET", None)
            else:
                os.environ["REPRO_PALLAS_INTERPRET"] = old
            ops.reset_interpret_cache()
