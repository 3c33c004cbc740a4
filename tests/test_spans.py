"""Profiler spans of the serve engine, the train loop and the control
plane (``repro.telemetry.span``, DESIGN_TELEMETRY.md §7), read back from
the ``.xplane.pb`` that ``jax.profiler`` writes on the CPU."""
import collections
import contextlib
import dataclasses
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.telemetry import span

Span = collections.namedtuple("Span", "name start end parent args")


def read_spans(trace_dir):
    """Every ``repro.*`` host span of the trace, each with the innermost
    ``repro.*`` span enclosing it on its thread as ``parent``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith("repro.")),
                         key=lambda x: (x[1], -x[2]))
            stack = []
            for name, s, e, args in evs:
                while stack and not (stack[-1].start <= s
                                     and e <= stack[-1].end):
                    stack.pop()
                sp = Span(name, s, e, stack[-1].name if stack else None,
                          args)
                out.append(sp)
                stack.append(sp)
    return out


@contextlib.contextmanager
def traced(tmp_path):
    """Profile the block; the list it yields holds the spans after it.
    The Python tracer stays off: it would slow the compiles tenfold."""
    spans = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield spans
    finally:
        jax.profiler.stop_trace()
    spans.extend(read_spans(str(tmp_path)))


def children(spans, parent):
    return [s for s in spans if parent.start <= s.start
            and s.end <= parent.end and s is not parent]


def test_span_takes_plain_counters_only():
    with span("repro.test", step=1, ratio=0.5, ok=True, tag="a") as sp:
        sp.count(more=2)
    with pytest.raises(TypeError, match="counter 'lanes'"):
        span("repro.test", lanes=jnp.ones((), jnp.int32))
    with pytest.raises(TypeError):
        span("repro.test", lanes=np.int64(3))
    with span("repro.test") as sp, pytest.raises(TypeError):
        sp.count(lanes=jnp.zeros(()))


def test_serve_engine_spans(tmp_path):
    from repro.launch.serve import Request, ServeEngine
    eng = ServeEngine("yi-6b", num_slots=2, max_len=32, seed=0,
                      page_size=4, prefill_chunk=4)
    assert eng.alloc is not None
    # compile the step before the trace opens
    eng.submit(Request(uid=100, prompt=np.arange(1, 4, dtype=np.int32),
                       max_new_tokens=1))
    while not eng.idle:
        eng.step()
    rng = np.random.default_rng(0)
    prompts = {uid: rng.integers(1, 100, size=P).astype(np.int32)
               for uid, P in ((0, 5), (1, 6), (2, 7))}
    with traced(tmp_path) as spans:
        for uid, p in prompts.items():
            eng.submit(Request(uid=uid, prompt=p, max_new_tokens=3))
        while not eng.idle:
            eng.step()
    done = {c.uid: c for c in eng.completions if c.uid in prompts}
    assert sorted(done) == [0, 1, 2]

    names = collections.Counter(s.name for s in spans)
    steps = [s for s in spans if s.name == "repro.serve.step"]
    assert len(steps) == names["repro.device"] > 0
    parents = {"repro.serve.admit": "repro.serve.step",
               "repro.serve.feed": "repro.serve.step",
               "repro.device": "repro.serve.step",
               "repro.serve.fetch": "repro.serve.step",
               "repro.serve.harvest": "repro.serve.step",
               "repro.serve.request_admitted": "repro.serve.admit",
               "repro.serve.request_done": "repro.serve.harvest",
               "repro.serve.step": None}
    assert set(names) == set(parents)
    for s in spans:
        assert s.parent == parents[s.name], s
    for k, st in enumerate(steps):
        assert set(st.args) == {"step", "active", "lanes", "admitted",
                                "completed", "preempted", "queued",
                                "pages_free"}
        assert st.args["step"] == steps[0].args["step"] + k
        kids = children(spans, st)
        feed, = [s for s in kids if s.name == "repro.serve.feed"]
        admit, = [s for s in kids if s.name == "repro.serve.admit"]
        harvest, = [s for s in kids if s.name == "repro.serve.harvest"]
        assert feed.args["lanes"] == st.args["lanes"]
        assert admit.args["admitted"] == st.args["admitted"] == sum(
            s.name == "repro.serve.request_admitted" for s in kids)
        assert harvest.args["completed"] == st.args["completed"] == sum(
            s.name == "repro.serve.request_done" for s in kids)
        assert 0 <= st.args["active"] <= 2
        assert st.args["preempted"] == 0
    # every position written is one valid lane: the prompt, then each
    # generated token but the last
    assert sum(s.args["lanes"] for s in steps) == sum(
        len(prompts[u]) + len(done[u].tokens) - 1 for u in prompts)
    admitted = {s.args["uid"]: s.args["queue_wait_ms"] for s in spans
                if s.name == "repro.serve.request_admitted"}
    finished = {s.args["uid"]: s.args["tokens"] for s in spans
                if s.name == "repro.serve.request_done"}
    assert set(admitted) == set(finished) == set(prompts)
    assert all(w >= 0.0 for w in admitted.values())
    # two slots: the third request waited for one to free up
    assert admitted[2] > max(admitted[0], admitted[1])
    assert finished == {u: len(done[u].tokens) for u in prompts}


def test_train_loop_spans(tmp_path):
    from repro.config import resolve_model
    from repro.launch.train import run_training
    from repro.models import get_api
    with traced(tmp_path) as spans:
        hist = run_training("vit-1b", steps=10, tp=1, batch=2, quiet=True,
                            control_mode="semi", hetero_kind="static",
                            chi=2.0)
    steps = [s for s in spans if s.name == "repro.train.step"]
    assert [s.args["step"] for s in steps] == list(range(10))
    for st in steps:
        kids = [s for s in children(spans, st)
                if s.parent == "repro.train.step"]
        assert collections.Counter(s.name for s in kids if s.name in (
            "repro.device", "repro.train.batch", "repro.train.fetch",
            "repro.control.decide", "repro.control.dispatch")) == {
            "repro.device": 1, "repro.train.batch": 1,
            "repro.train.fetch": 1, "repro.control.decide": 1,
            "repro.control.dispatch": 1}
    # tp=1 plans one signature, built with the plane: no dispatch builds
    dispatch = [s for s in spans if s.name == "repro.control.dispatch"]
    assert sum(s.args["new_signature"] for s in dispatch) \
        == hist["plan_compiles"] - 1 == 0

    # the weight statistics, once, on the tenth step
    stats, = [s for s in spans if s.name == "repro.control.weight_stats"]
    assert stats.parent == "repro.train.step"
    fetches = [s for s in spans
               if s.name == "repro.control.weight_stats.fetch"]
    assert all(s.parent == "repro.control.weight_stats" for s in fetches)
    cfg = resolve_model("vit-1b")
    shapes = jax.eval_shape(lambda: get_api(cfg).init(
        jax.random.PRNGKey(0), cfg, jnp.float32)[0])
    want = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        keys = tuple(str(getattr(k, "key", k)) for k in path)
        if keys[-2:] in (("ffn", "w_down"), ("attn", "wq"), ("attn", "wo")):
            want[keys[-2:]] = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    assert len(want) == 3
    assert sorted(s.args["bytes"] for s in fetches) == sorted(want.values())
    assert stats.args["bytes"] == sum(want.values())
    observe, = [s for s in spans if s.name == "repro.control.observe_weights"]
    assert observe.args["scopes"] == 3 and observe.start >= stats.end


def test_dispatch_marks_each_new_signature(tmp_path):
    """A plane at tp=4 under contention dispatches several signatures;
    ``new_signature`` is 1 on the first dispatch of each, the plane's
    own base signature excepted."""
    from repro.config import WorkloadControlConfig, get_config, smoke_variant
    from repro.control import ControlPlane
    from repro.core.hetero import IterationModel
    from repro.core.workload import PlanCompileCache
    wc = WorkloadControlConfig(enabled=True, mode="semi", block_size=8,
                               max_migration_sources=2,
                               migration_shed_cap=2)
    plane = ControlPlane(
        smoke_variant(get_config("yi-6b")), wc, mesh=None, tp=4,
        builder=lambda st: (object(), max(1, st.num_sources)
                            if st is not None else 0, None),
        it_model=IterationModel(matmul_time=1.0, other_time=0.15),
        hetero_kind="contention", chi=3.0, contention_p=0.3, seed=0)
    seen = {PlanCompileCache.key_for(plane.static)}
    want = []
    with traced(tmp_path) as spans:
        for step in range(20):
            plan, _ = plane.decide(plane.controller_times(plane.chis(step)))
            _, _, proj = plane.dispatch(plan)
            key = PlanCompileCache.key_for(dataclasses.replace(
                plane.static, mig_shed=proj.mig_sheds, mig_blocks=0))
            want.append(int(key not in seen))
            seen.add(key)
    got = [s.args["new_signature"] for s in spans
           if s.name == "repro.control.dispatch"]
    assert got == want and sum(want) >= 2
    assert sum(want) == plane.cache.compile_count - 1
    assert sum(s.name == "repro.control.decide" for s in spans) == 20
