"""The serve driver end to end on the CPU at a tiny size: the timed path
checks out against the reference, and a token altered where the engine
produces it makes the run not correct."""
import numpy as np
import pytest

from chipbench.drivers import serve
from chipbench.tests import tiny


def _run(ctx):
    out = serve.run(ctx)
    return out, {c["name"]: c for c in out["checks"]}


def test_tiny_serve_is_correct_and_counted():
    ctx = tiny.serve_context(seed=2 ** 31 + 3)
    out, checks = _run(ctx)
    assert all(c["ok"] for c in checks.values()), checks
    assert checks["served_logit_gap_max"]["value"] == pytest.approx(
        0.0, abs=1e-4)
    c = out["counters"]
    assert 0 < c["lanes_valid"] <= c["lanes_total"]
    assert c["tokens"] == pytest.approx(
        out["e2e"]["serve_tokens_per_s"] * ctx.seconds)
    assert out["attempted"] == len(c["ttft_ms"]) == len(c["queue_wait_ms"])
    assert out["e2e"]["itl_p95_ms"] > 0 and out["e2e"]["ttft_p90_ms"] > 0


def test_altered_token_is_not_correct(monkeypatch):
    """Every step, slot 0's produced token ids are shifted by one."""
    build = serve.build_engine

    def faulty(ctx):
        eng = build(ctx)
        step = eng._base_step
        vocab = eng.cfg.vocab_size

        def altered(*args):
            toks, cache = step(*args)
            return toks.at[:, 0].set((toks[:, 0] + 1) % vocab), cache
        eng._base_step = altered
        return eng

    monkeypatch.setattr(serve, "build_engine", faulty)
    ctx = tiny.serve_context(seed=11)
    _, checks = _run(ctx)
    gap = checks["served_logit_gap_max"]
    assert not gap["ok"] and gap["value"] > gap["limit"]


def test_gap_and_sample_rules():
    logits = np.array([[0.0, 2.0, 1.0], [3.0, 0.5, 0.0]], np.float32)
    assert serve.gap_of(logits, np.array([1, 0])) == 0.0
    assert serve.gap_of(logits, np.array([2, 1])) == pytest.approx(2.5)
    reqs = [serve.Req(i, np.zeros(4, np.int32), n, 0.0,
                      tokens=np.zeros(n, np.int32))
            for i, n in enumerate([5, 40, 7, 9, 3])]
    pick = serve.pick_sample(reqs, seed=1, want_tokens=45, most=8)
    assert pick[0].uid == 1 and sum(len(r.tokens) for r in pick) >= 45
    assert serve.pick_sample(reqs, 1, 45, 8) == pick
    seqs, want, served = serve.reference_inputs(pick[:1])
    assert len(seqs[0]) == 4 + 39 and list(want[0]) == list(range(3, 43))
    assert served.shape == (40,)


def test_int8_control_reads_well_above_bf16_serving():
    """The control, the float32 reference computed with int8 matmul
    inputs, its first-ranked tokens judged as served ones through the
    run's own checks, against bf16 serving at a size a test can hold
    (hidden 512, 4 layers, vocab 4096). The limit for this size is set
    as the cell's was, from readings (CPU, seeds 12-14): the program
    0.0051-0.0072, the control 0.032-0.045; limit 0.015."""
    m = dict(tiny.TINY_LM, hidden_size=512, intermediate_size=1024,
             num_hidden_layers=4, vocab_size=4096, num_attention_heads=8,
             num_key_value_heads=2)
    ctx = tiny.serve_context(seed=12, control=True, seconds=4.0, slots=8,
                             max_len=96, model=m,
                             output={"median": 24, "sigma": 0.5, "min": 8,
                                     "max": 48})
    ctx.config["engine"]["param_dtype"] = "bfloat16"
    ctx.config["correctness"].update(sample_tokens=200, sample_requests=12,
                                     logit_gap_limit=0.015)
    out, checks = _run(ctx)
    assert all(c["ok"] for c in checks.values()), checks
    prog = checks["served_logit_gap_max"]["value"]
    control = out["counters"]["control_logit_gap_max"]
    assert out["counters"]["control_correct"] is False, (prog, control)
    # three times the largest program reading at this size, or this one:
    # the window is timed, so which requests finish varies with the host
    assert control >= 3 * max(prog, 0.0072), (prog, control)


def test_seeded_weights_are_the_engines_own(monkeypatch):
    """The weights the engine's checkpoint read is handed are those the
    engine makes itself from the same seed, bit for bit, and it keeps
    the buffers it was handed rather than a copy."""
    import jax
    from repro.control import ControlConfig
    from repro.launch.serve import ServeEngine
    ctx = tiny.serve_context(seed=2 ** 31 + 77)
    ctx.config["engine"]["param_dtype"] = "bfloat16"
    e = ctx.config["engine"]
    own = ServeEngine(serve.model_config(ctx.config),
                      num_slots=e["num_slots"], max_len=e["max_len"],
                      page_size=e["page_size"],
                      prefill_chunk=e["prefill_chunk"],
                      param_dtype=e["param_dtype"],
                      control=ControlConfig(mode="off",
                                            fused_attention=False),
                      seed=ctx.seed)
    handed = {}
    load = serve.SeededStore.load_latest_params

    def spy(self, directory, like):
        handed["params"] = load(self, directory, like)[1]
        return 0, handed["params"]

    monkeypatch.setattr(serve.SeededStore, "load_latest_params", spy)
    ours = serve.build_engine(ctx)
    mine, theirs = jax.tree.leaves(ours.params), jax.tree.leaves(own.params)
    assert len(mine) == len(theirs) > 0
    for a, b, h in zip(mine, theirs, jax.tree.leaves(handed["params"])):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
        assert a.unsafe_buffer_pointer() == h.unsafe_buffer_pointer()


def test_traced_run_brackets_the_end_of_the_window():
    """On the CPU the profiler records host spans but no TPU plane, so the
    reduction finds nothing to read; the traced steps are the last ones."""
    from chipbench import harness
    ctx = tiny.serve_context(seed=5, seconds=2.0)
    ctx.tracer = harness.Tracer(True, 1)
    ctx.config["trace_seconds"] = 0.5
    out, checks = _run(ctx)
    assert all(c["ok"] for c in checks.values())
    assert 0 < out["counters"]["traced_steps"] < out["counters"]["steps"]
    assert ctx.tracer.reduce() == {}
