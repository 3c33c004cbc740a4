"""Known-bad variants that prove each rule actually fires.

``--mutate`` seeds one deliberate violation per rule — a retrace that
forks (R1), a host callback and a dropped donation (R2), psum chunking
silently ignored (R3), an oversubscribed Pallas tile (R4), an f64
promotion (R5) — and asserts the corresponding rule reports it. A rule
that stays silent on its mutant is a dead rule; CI fails on that just
as hard as on a dirty HEAD.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

from repro.analysis import engine
from repro.analysis import registry as reg

# Hand-written bad HLO for the no-device R3 fallback: psum_chunks=4 was
# requested but the module kept the single fat full-width all-reduce.
_R3_BAD_HLO = """\
HloModule mutant_chunks_ignored, entry_computation_layout={(f32[2,8,256]{2,1,0})->f32[2,8,256]{2,1,0}}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[2,8,256]) -> f32[2,8,256] {
  %p0 = f32[2,8,256]{2,1,0} parameter(0)
  ROOT %ar = f32[2,8,256]{2,1,0} all-reduce(f32[2,8,256]{2,1,0} %p0), replica_groups={}, to_apply=%sum
}
"""


def _sds(shape, dtype="float32"):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _m_retrace_forks(env: reg.CaseEnv) -> List[reg.Artifact]:
    """R1: two builds of the "same" plan signature bake different
    constants into the step — the moral equivalent of keying the
    compile cache on a non-canonical plan signature. (Two fresh builder
    closures, because jax's trace cache makes re-tracing one fn object
    trivially stable.)"""
    def build(c):
        return lambda x: x * c

    x = _sds((8,))
    case = reg.TraceCase(
        step="mutant", name="retrace_forks", fn=build(1.0), args=(x,),
        retrace=(("rebuild-same-signature", build(2.0), (x,)),))
    return [engine.trace_artifact(case, env)]


def _m_host_callback(env: reg.CaseEnv) -> List[reg.Artifact]:
    """R2: a pure_callback smuggled into the hot step."""
    import jax
    import numpy as np

    def fn(x):
        y = jax.pure_callback(lambda a: np.asarray(a),
                              jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1.0

    case = reg.TraceCase(step="mutant", name="host_callback", fn=fn,
                         args=(_sds((8,)),))
    return [engine.trace_artifact(case, env)]


def _m_donation_dropped(env: reg.CaseEnv) -> List[reg.Artifact]:
    """R2: a state buffer (argnum 1, think KV cache) declared hot but
    NOT in donate_argnums."""
    def fn(p, cache):
        return p, cache + 1.0

    case = reg.TraceCase(step="mutant", name="donation_dropped", fn=fn,
                         args=(_sds((4,)), _sds((4, 8))),
                         state_argnums=(1,), donate_argnums=())
    return [engine.trace_artifact(case, env)]


def _m_chunks_ignored(env: reg.CaseEnv) -> List[reg.Artifact]:
    """R3: the plan says psum_chunks=4 but the compiled module kept one
    fat full-width all-reduce. With >= 8 host devices this compiles the
    REAL controlled projection built with psum_chunks=1 and lints it
    against the chunks=4 expectation; otherwise a handwritten bad module
    stands in."""
    expect = {"chunked_all_reduce": {
        "chunks": 4, "full_dims": "2,8,256", "chunk_dims": "2,8,64"}}
    if env.compile_hlo and env.max_devices >= 8:
        from repro.analysis import micro
        good = micro._collective_cases(env)
        k1 = next(c for c in good if c.name == "proj_psum_chunks1")
        bad = reg.TraceCase(step="mutant", name="chunks_ignored",
                            fn=k1.fn, args=k1.args, mesh=k1.mesh,
                            compile_hlo=True, expect=expect)
        return [engine.trace_artifact(bad, env)]
    case = reg.TraceCase(step="mutant", name="chunks_ignored",
                         fn=lambda: None, args=(), expect=expect)
    return [reg.Artifact(case=case, hlo_text=_R3_BAD_HLO)]


def _m_vmem_blowout(env: reg.CaseEnv) -> List[reg.Artifact]:
    """R4: the fused FFN kernel at a hidden width whose default tiles
    oversubscribe the 16 MiB budget."""
    from repro.kernels import ops

    def fn(x, wu, wd, k):
        import jax
        return ops.fused_pruned_ffn(x, wu, wd, k, None, jax.nn.silu)

    case = reg.TraceCase(
        step="mutant", name="vmem_blowout", fn=fn,
        args=(_sds((256, 4096)), _sds((4096, 8192)), _sds((8192, 4096)),
              _sds((32,), "int32")))
    return [engine.trace_artifact(case, env)]


def _m_f64_leak(env: reg.CaseEnv) -> List[reg.Artifact]:
    """R5: an accidental float64 promotion inside the step."""
    import jax

    def fn(x):
        return x.astype("float64") * 2.0

    case = reg.TraceCase(step="mutant", name="f64_leak", fn=fn,
                         args=(_sds((8,)),))
    with jax.enable_x64(True):
        return [engine.trace_artifact(case, env)]


#: rule id -> (mutant name, artifact builder)
MUTANTS: Tuple[Tuple[str, str, Callable], ...] = (
    ("R1", "retrace_forks", _m_retrace_forks),
    ("R2", "host_callback", _m_host_callback),
    ("R2", "donation_dropped", _m_donation_dropped),
    ("R3", "chunks_ignored", _m_chunks_ignored),
    ("R4", "vmem_blowout", _m_vmem_blowout),
    ("R5", "f64_leak", _m_f64_leak),
)


def run_mutants(env: reg.CaseEnv = None
                ) -> Dict[str, Tuple[bool, str]]:
    """Returns {mutant_name: (rule_fired, detail)}. Every entry must
    fire for the analyzer itself to be considered alive."""
    env = env or reg.CaseEnv()
    out: Dict[str, Tuple[bool, str]] = {}
    for rule_id, name, build in MUTANTS:
        try:
            arts = build(env)
        except Exception as e:                            # noqa: BLE001
            out[name] = (False, f"mutant build failed: {e!r}")
            continue
        errs = [a.error for a in arts if a.error]
        if errs:
            out[name] = (False, f"mutant trace failed: {errs}")
            continue
        hits = [v for v in engine.lint(arts, [rule_id])
                if v.rule == rule_id]
        if hits:
            out[name] = (True, str(hits[0]))
        else:
            out[name] = (False,
                         f"rule {rule_id} did NOT fire on its mutant")
    return out
