"""Tests for the static analyzer itself (ISSUE 10, repro.analysis):
known-good/known-bad fixtures per rule R1-R5, registry completeness,
the mutate-mode smoke, and the banned-API source scans that back the
ruff TID251 rules for environments without ruff."""
import os
import re
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import rules
from repro.analysis.engine import lint
from repro.analysis.registry import Artifact, TraceCase

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _case(**kw):
    kw.setdefault("step", "t")
    kw.setdefault("name", "c")
    kw.setdefault("fn", lambda: None)
    kw.setdefault("args", ())
    return TraceCase(**kw)


def _rules_fired(arts, rule_id):
    return [v for v in lint(arts, [rule_id]) if v.rule == rule_id]


# ---------------------------------------------------------------------------
# R1 — retrace audit
# ---------------------------------------------------------------------------


def test_r1_clean_when_hashes_agree():
    a = Artifact(case=_case(signature="sig"), jaxpr_hash="aaaa",
                 retrace_hashes=(("double-trace", "aaaa"),))
    b = Artifact(case=_case(name="c2", signature="sig"), jaxpr_hash="aaaa")
    assert _rules_fired([a, b], "R1") == []


def test_r1_fires_on_forked_retrace():
    a = Artifact(case=_case(), jaxpr_hash="aaaa",
                 retrace_hashes=(("alias-build", "bbbb"),))
    assert _rules_fired([a], "R1")


def test_r1_fires_on_signature_bucket_split():
    a = Artifact(case=_case(name="c1", signature="sig"), jaxpr_hash="aaaa")
    b = Artifact(case=_case(name="c2", signature="sig"), jaxpr_hash="bbbb")
    assert _rules_fired([a, b], "R1")


# ---------------------------------------------------------------------------
# R2 — host-sync / donation
# ---------------------------------------------------------------------------

_ALIASED_HLO = """
HloModule jit_step, input_output_alias={ {1}: (1, {}, may-alias) }
ENTRY main {
  %p0 = f32[4]{0} parameter(0)
  %p1 = f32[4,8]{1,0} parameter(1)
  ROOT %out = f32[4,8]{1,0} add(%p1, %p1)
}
"""

_INFEED_HLO = """
HloModule jit_step
ENTRY main {
  %tok = token[] after-all()
  %in = ((f32[4]{0}), token[]) infeed(%tok)
  ROOT %out = f32[4]{0} get-tuple-element(%in), index=0
}
"""


def test_r2_clean_on_donated_and_aliased_state():
    a = Artifact(case=_case(state_argnums=(1,), donate_argnums=(1,)),
                 hlo_text=_ALIASED_HLO)
    assert _rules_fired([a], "R2") == []


def test_r2_fires_on_undonated_state():
    a = Artifact(case=_case(state_argnums=(1,), donate_argnums=()))
    hits = _rules_fired([a], "R2")
    assert hits and "not donated" in hits[0].message


def test_r2_fires_when_declared_donation_did_not_alias():
    a = Artifact(case=_case(state_argnums=(1,), donate_argnums=(1,)),
                 hlo_text=_INFEED_HLO.replace("infeed", "add2"))
    hits = _rules_fired([a], "R2")
    assert hits and "input_output_alias" in hits[0].message


def test_r2_fires_on_hlo_host_transfer():
    a = Artifact(case=_case(), hlo_text=_INFEED_HLO)
    hits = _rules_fired([a], "R2")
    assert hits and "infeed" in hits[0].message


def test_r2_fires_on_callback_primitive():
    import jax
    import numpy as np

    def fn(x):
        return jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    jx = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((4,), "float32"))
    a = Artifact(case=_case(), jaxpr=jx)
    hits = _rules_fired([a], "R2")
    assert hits and "pure_callback" in hits[0].message


# ---------------------------------------------------------------------------
# R3 — collective audit
# ---------------------------------------------------------------------------

_CHUNKED_HLO = """
ENTRY main {
  %a1 = f32[2,8,64]{2,1,0} all-reduce(%x0), to_apply=%add
  %a2 = f32[2,8,64]{2,1,0} all-reduce(%x1), to_apply=%add
  %a3 = f32[2,8,64]{2,1,0} all-reduce(%x2), to_apply=%add
  %a4 = f32[2,8,64]{2,1,0} all-reduce(%x3), to_apply=%add
}
"""

_FAT_HLO = """
ENTRY main {
  %a1 = f32[2,8,256]{2,1,0} all-reduce(%x0), to_apply=%add
}
"""


def test_r3_chunked_audit_good_and_bad():
    ok, observed = rules.audit_chunked_all_reduce(
        _CHUNKED_HLO, 4, "2,8,256", "2,8,64")
    assert ok == [] and observed == ["2,8,64"] * 4
    bad, _ = rules.audit_chunked_all_reduce(
        _FAT_HLO, 4, "2,8,256", "2,8,64")
    assert len(bad) == 2          # missing chunks AND a surviving fat one
    ok1, _ = rules.audit_chunked_all_reduce(
        _FAT_HLO, 1, "2,8,256", "2,8,64")
    assert ok1 == []


def test_r3_rule_reads_expectations_from_case():
    exp = {"chunked_all_reduce": {
        "chunks": 4, "full_dims": "2,8,256", "chunk_dims": "2,8,64"}}
    good = Artifact(case=_case(expect=exp), hlo_text=_CHUNKED_HLO)
    bad = Artifact(case=_case(expect=exp), hlo_text=_FAT_HLO)
    assert _rules_fired([good], "R3") == []
    assert _rules_fired([bad], "R3")


def test_r3_grouped_psum_jaxpr_counting():
    import jax
    import jax.numpy as jnp

    sds = jax.ShapeDtypeStruct((4, 4), jnp.float32)

    def grouped(a, b):
        return jax.lax.psum((a, b), "i")

    def split(a, b):
        return jax.lax.psum(a, "i"), jax.lax.psum(b, "i")

    def trace(fn):
        mesh = jax.sharding.Mesh(
            __import__("numpy").array(jax.devices()[:1]), ("i",))
        from jax.sharding import PartitionSpec as P
        return jax.make_jaxpr(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False))(sds, sds)

    assert rules.grouped_psum_count_jaxpr(trace(grouped)) == 1
    assert rules.grouped_psum_count_jaxpr(trace(split)) == 0
    exp = {"grouped_psum": {"count": 1}}
    good = Artifact(case=_case(expect=exp), jaxpr=trace(grouped))
    bad = Artifact(case=_case(expect=exp), jaxpr=trace(split))
    assert _rules_fired([good], "R3") == []
    assert _rules_fired([bad], "R3")


# ---------------------------------------------------------------------------
# R4 — Pallas VMEM budget
# ---------------------------------------------------------------------------


def _matmul_jaxpr():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    sds = jax.ShapeDtypeStruct
    return jax.make_jaxpr(lambda x, w, k: ops.block_pruned_matmul(
        x, w, k, 32, 16, 32))(
        sds((16, 128), jnp.float32), sds((128, 64), jnp.float32),
        sds((2,), jnp.int32))


def test_r4_clean_within_budget_fires_when_budget_shrunk():
    jx = _matmul_jaxpr()
    good = Artifact(case=_case(), jaxpr=jx)
    assert _rules_fired([good], "R4") == []
    bad = Artifact(case=_case(expect={"vmem_budget": 1024}), jaxpr=jx)
    hits = _rules_fired([bad], "R4")
    assert hits and "VMEM" in hits[0].message


def test_r4_assert_fits_raises_named_error():
    import jax
    import jax.numpy as jnp
    from repro.analysis.vmem import VmemBudgetError, assert_fits
    from repro.kernels import ops
    sds = jax.ShapeDtypeStruct
    args = (sds((16, 128), jnp.float32), sds((128, 64), jnp.float32),
            sds((2,), jnp.int32))
    assert_fits(lambda x, w, k: ops.block_pruned_matmul(x, w, k, 32, 16, 32),
                *args)                                    # default budget ok
    with pytest.raises(VmemBudgetError):
        assert_fits(
            lambda x, w, k: ops.block_pruned_matmul(x, w, k, 32, 16, 32),
            *args, budget=1024)


# ---------------------------------------------------------------------------
# R5 — dtype leak
# ---------------------------------------------------------------------------


def test_r5_fires_on_f64_in_hlo_and_respects_allowance():
    hlo = "ENTRY main {\n  %c = f64[8]{0} convert(%p0)\n}\n"
    bad = Artifact(case=_case(), hlo_text=hlo)
    assert _rules_fired([bad], "R5")
    allowed = Artifact(case=_case(expect={"allow_f64": True}),
                       hlo_text=hlo)
    assert _rules_fired([allowed], "R5") == []
    clean = Artifact(case=_case(),
                     hlo_text="ENTRY main {\n  %c = f32[8]{0} convert(%p0)\n}\n")
    assert _rules_fired([clean], "R5") == []


def test_r5_fires_on_f64_jaxpr():
    import jax
    with jax.enable_x64(True):
        jx = jax.make_jaxpr(lambda x: x.astype("float64") * 2)(
            jax.ShapeDtypeStruct((4,), "float32"))
    assert _rules_fired([Artifact(case=_case(), jaxpr=jx)], "R5")


# ---------------------------------------------------------------------------
# engine-level behavior
# ---------------------------------------------------------------------------


def test_engine_surfaces_trace_failures_as_violations():
    broken = _case(fn=lambda: (_ for _ in ()).throw(RuntimeError("boom")),
                   args=())
    from repro.analysis.engine import trace_artifact
    from repro.analysis.registry import CaseEnv
    art = trace_artifact(broken, CaseEnv())
    assert art.error
    hits = [v for v in lint([art]) if v.rule == "engine"]
    assert hits and "boom" in hits[0].message


def test_registry_completeness_every_cli_step_registered():
    from repro.analysis.registry import REQUIRED_STEPS, load_providers
    names = load_providers()
    missing = set(REQUIRED_STEPS) - set(names)
    assert not missing, (
        f"step builders missing analysis registration: {sorted(missing)} — "
        "register them via repro.analysis.registry (DESIGN_ANALYSIS.md)")


def test_unknown_rule_id_rejected():
    with pytest.raises(ValueError):
        rules.rules_by_id(["R9"])


def test_hlo_shim_modules_warn_and_forward():
    import importlib
    import warnings
    from repro.analysis import hlo as canonical
    for shim_name, attr in (("repro.launch.hlo_analysis",
                             "parse_collectives"),
                            ("repro.launch.hlo_inspect", "op_histogram")):
        shim = importlib.import_module(shim_name)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            fn = getattr(shim, attr)
        assert any(issubclass(x.category, DeprecationWarning) for x in w), \
            shim_name
        assert fn is getattr(canonical, attr)


# ---------------------------------------------------------------------------
# mutate-mode smoke (subprocess: forced host devices, real CLI)
# ---------------------------------------------------------------------------


def test_mutate_mode_every_rule_fires():
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--mutate",
         "--devices", "8"],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")})
    assert out.returncode == 0, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "0 silent" in out.stdout


# ---------------------------------------------------------------------------
# banned-API source scans (TID251 backstop for ruff-less environments)
# ---------------------------------------------------------------------------


def _source_files():
    for base in ("src", "benchmarks"):
        for dirpath, _, files in os.walk(os.path.join(ROOT, base)):
            for f in files:
                if f.endswith(".py"):
                    yield os.path.join(dirpath, f)


def _code_lines(path):
    """Source lines with #-comments stripped (coarse, string-safe enough
    for a banned-pattern scan)."""
    for line in open(path, encoding="utf-8"):
        yield line.split("#", 1)[0]


def test_no_id_calls_on_request_objects():
    """PR 8 regression class: ``id(req)`` as a request key aliases
    recycled objects (the TTFT clock bug). Request identity is
    ``req.uid``, always."""
    pat = re.compile(r"\bid\(\s*(?:req|request)\b")
    bad = [p for p in _source_files()
           if any(pat.search(ln) for ln in _code_lines(p))]
    assert not bad, f"id() called on request objects in: {bad}"


def test_no_direct_hlo_analysis_imports_outside_analysis_package():
    pat = re.compile(r"(?:from\s+repro\.launch\s+import\s+[^\n]*"
                     r"\bhlo_analysis\b|"
                     r"(?:from|import)\s+repro\.launch\.hlo_analysis\b)")
    allowed = {os.path.join(ROOT, "src", "repro", "launch",
                            "hlo_analysis.py")}
    bad = [p for p in _source_files()
           if p not in allowed
           and pat.search(open(p, encoding="utf-8").read())]
    assert not bad, (
        f"direct repro.launch.hlo_analysis imports (use "
        f"repro.analysis.hlo): {bad}")
