"""Open-loop serving through ``repro.launch.serve.ServeEngine``.

The driver owns the clock. Requests from the cell's traffic file become
due at fixed offsets from the window's opening; each is submitted when
due (``ServeEngine.submit``) and the engine is stepped
(``ServeEngine.step``) whenever it holds work. Every request is timed on
the host clock from the moment it was due; the engine's modeled
``token_latencies`` are never read. A token counts at the host time its
step returned, which is when the engine hands it back.

Set-up builds the engine, whose checkpoint read hands it the weights of
the program's own initialisation from the seed, made on the device by a
program that takes the key as its argument (``SeededStore``), submits
the traffic's warm requests together and steps until all of them hold a
slot and one has its first token, so the only step program is compiled
and the server is at steady occupancy when the window opens.

After the window: the peak memory is read, the engine is freed, and a
sample of finished requests drawn from the seed, the longest among them,
goes through the plain float32 reference (``chipbench.reference.lm``).
The number compared is the widest gap by which a served token's
reference logit lies below the reference's best logit at its position.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import harness
from chipbench import traffic as traffic_lib
from chipbench import work
from chipbench.metrics._common import pct

#: the published config's keys -> the program's ModelConfig fields
MODEL_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
              "num_attention_heads": "num_heads",
              "num_key_value_heads": "num_kv_heads",
              "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
              "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
              "head_dim": "head_dim"}


def model_config(conf: dict):
    """The program's ModelConfig for the configuration file, as run."""
    from repro.config import get_config
    m = conf["model"]
    cfg = get_config(conf["program_arch"])
    cfg = dataclasses.replace(
        cfg, **{f: m[k] for k, f in MODEL_KEYS.items() if k in m},
        head_dim=m.get("head_dim")
        or m["hidden_size"] // m["num_attention_heads"])
    if m.get("hidden_act", "silu") != cfg.act or \
            bool(m.get("tie_word_embeddings", False)) != cfg.tie_embeddings:
        raise ValueError("configuration's activation or tying differs "
                         "from the program's architecture")
    return cfg


@dataclasses.dataclass
class Req:
    uid: int
    prompt: np.ndarray
    max_new: int
    due: float                    # host clock
    admitted: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: Optional[np.ndarray] = None


class SeededStore:
    """Stands in for the engine's checkpoint store, whose one read at
    construction, ``load_latest_params``, hands back the seed's weights
    (``harness.seeded_init``)."""

    def __init__(self, cfg, dtype, seed: int):
        self.cfg, self.dtype, self.seed = cfg, dtype, seed

    def load_latest_params(self, directory, like):
        return 0, harness.seeded_init(self.cfg, self.dtype, self.seed,
                                      like)


def build_engine(ctx):
    import jax.numpy as jnp
    from repro.control import ControlConfig
    from repro.launch import serve as serve_mod
    e = ctx.config["engine"]
    cfg = model_config(ctx.config)
    store = SeededStore(cfg, jnp.dtype(e["param_dtype"]), ctx.seed)
    with harness.patched(serve_mod, ckpt_store=store):
        return serve_mod.ServeEngine(
            cfg, num_slots=e["num_slots"], max_len=e["max_len"],
            page_size=e["page_size"], prefill_chunk=e["prefill_chunk"],
            param_dtype=e["param_dtype"],
            control=ControlConfig(mode="off",
                                  fused_attention=e["fused_attention"]),
            seed=ctx.seed, ckpt_dir="seeded-weights")


class Loop:
    """Submits, steps and books. Per step it counts the (token, slot)
    lanes fed, from the engine's chunking rule (a slot still in its
    prompt feeds up to ``prefill_chunk`` positions, a decoding slot one),
    and the context length each lane attends over."""

    def __init__(self, eng, ctx):
        self.eng = eng
        self.ctx = ctx
        self.C = eng.prefill_chunk
        self.reqs: Dict[int, Req] = {}
        self.steps = []           # (t0, t1, lanes, keys, emitted)
        self.step_events = []     # (admitted, completed) per step
        self._uid = 0

    def next_uid(self) -> int:
        self._uid += 1
        return self._uid - 1

    def submit(self, r: Req) -> bool:
        from repro.launch.serve import Request
        self.reqs[r.uid] = r
        return self.eng.submit(Request(uid=r.uid, prompt=r.prompt,
                                       max_new_tokens=r.max_new))

    def _feeds(self, slots):
        lanes = keys = 0
        for s in slots:
            P = len(s.req.prompt)
            n = min(self.C, P - s.pos) if s.pos < P else 1
            lanes += n
            keys += sum(s.pos + i + 1 for i in range(n))
        return lanes, keys

    def step(self):
        eng = self.eng
        before = [s for s in eng.slots if s is not None]
        lanes, keys = self._feeds(before)
        t0 = time.perf_counter()
        with self.ctx.tracer.span("bench.engine_step"):
            rep = eng.step()
        t1 = time.perf_counter()
        done = {c.uid: c for c in eng.completions[-len(rep["completed"]):]} \
            if rep["completed"] else {}
        for uid in rep["admitted"]:
            self.reqs[uid].admitted = t0
            P = len(self.reqs[uid].prompt)
            n = min(self.C, P)
            lanes += n
            keys += n * (n + 1) // 2
        emitted = 0
        live = {s.req.uid: s for s in eng.slots if s is not None}
        for uid in set(live) | set(done):
            r = self.reqs[uid]
            got = (len(done[uid].tokens) if uid in done
                   else len(live[uid].generated))
            new = got - len(r.times)
            r.times.extend([t1] * new)
            emitted += new
            if uid in done:
                r.tokens = np.asarray(done[uid].tokens)
        self.steps.append((t0, t1, lanes, keys, emitted))
        self.step_events.append((len(rep["admitted"]),
                                 len(rep["completed"])))
        return rep

    @property
    def busy(self) -> bool:
        return not self.eng.idle


def setup(ctx, warm):
    """Build the engine and bring it to steady occupancy: submit the warm
    requests together and step until every one of them holds a slot and
    the first first token is out (the step program has then run)."""
    t = time.perf_counter()
    eng = build_engine(ctx)
    t_built = time.perf_counter()
    loop = Loop(eng, ctx)
    for prompt, n in warm:
        loop.submit(Req(loop.next_uid(), prompt, n, t_built))
    while eng.queue or not any(r.times for r in loop.reqs.values()):
        loop.step()
    t_warm = time.perf_counter()
    print(f"serve: set-up engine {t_built - t:.2f} s, first step "
          f"{loop.steps[0][1] - loop.steps[0][0]:.2f} s, "
          f"{len(loop.steps)} warm steps {t_warm - t_built:.2f} s",
          file=sys.stderr, flush=True)
    return eng, loop


def window(ctx, loop, requests, t_open, seconds):
    """Drive the open loop from ``t_open`` for ``seconds``: each request
    ``(due_s, prompt, max_new)`` is submitted once due, and the engine
    steps while it holds work. A traced run traces the window's last
    ``trace_seconds``, once the warm requests' prefill is long past.
    Returns (due requests, failed, index of the first window step,
    indices of the traced steps)."""
    t_close = t_open + seconds
    trace_from = t_close - min(float(ctx.config.get("trace_seconds", 5.0)),
                               seconds)
    pending = [Req(loop.next_uid(), p, n, t_open + due)
               for due, p, n in requests]
    first = len(loop.steps)
    tracing = False
    traced, failed, i = [], 0, 0
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        if ctx.tracer.on and not tracing and now >= trace_from:
            ctx.tracer.start()
            tracing = True
        with ctx.tracer.span("bench.submit"):
            while i < len(pending) and pending[i].due <= now:
                if not loop.submit(pending[i]):
                    failed += 1
                i += 1
        if loop.busy:
            loop.step()
            if tracing:
                traced.append(len(loop.steps) - 1)
        else:
            nxt = pending[i].due if i < len(pending) else t_close
            with ctx.tracer.span("bench.wait_for_arrival"):
                time.sleep(max(0.0, min(nxt, t_close) - now))
    ctx.tracer.stop()
    return pending, failed, first, traced


def stats(ctx, loop, due, first, traced, t_open, seconds):
    """End-to-end numbers and per-layer counters of one window."""
    t_close = t_open + seconds
    tokens_in = sum(1 for r in loop.reqs.values() for t in r.times
                    if t_open <= t <= t_close)
    itl = [(b - a) * 1e3 for r in loop.reqs.values()
           for a, b in zip(r.times, r.times[1:])
           if t_open <= a and b <= t_close]
    ttft = [((r.times[0] if r.times and r.times[0] <= t_close else t_close)
             - r.due) * 1e3 for r in due]
    qwait = [((r.admitted if r.admitted is not None
               and r.admitted <= t_close else t_close) - r.due) * 1e3
             for r in due]
    # a slow step delays every busy slot's gap at once, so the gaps come
    # in clumps of ~slots: p95 leaves ~10 steps beyond it, p99 ~2
    e2e = {"serve_tokens_per_s": tokens_in / seconds,
           "itl_p95_ms": pct(itl, 95), "ttft_p90_ms": pct(ttft, 90)}
    win = [s for s in loop.steps[first:] if s[1] <= t_close]
    tr = [loop.steps[k] for k in traced]
    m = ctx.config["model"]
    lanes = sum(s[2] for s in win)

    def flops(steps):
        return (sum(s[2] for s in steps) * work.lm_token_flops(m)
                + work.lm_attn_flops(m, sum(s[3] for s in steps))
                + sum(s[4] for s in steps) * work.lm_head_flops(m))

    counters = {
        "window_s": seconds, "steps": len(win), "tokens": tokens_in,
        "lanes_valid": lanes,
        "lanes_total": loop.C * loop.eng.num_slots * len(win),
        "model_flops": flops(win), "traced_model_flops": flops(tr),
        "queue_wait_ms": qwait, "ttft_ms": ttft, "itl_ms": itl,
        "step_ms": [(s[1] - s[0]) * 1e3 for s in win],
        "traced_steps": len(tr),
        "traced_attn_flops": work.lm_attn_flops(m, sum(s[3] for s in tr)),
        "traced_attn_bytes": work.lm_attn_bytes(m, sum(s[3] for s in tr)),
        "queued_at_close": len(loop.eng.queue),
    }
    return e2e, counters


def run(ctx) -> dict:
    conf, tr = ctx.config, ctx.traffic
    traffic_lib.check_fits(tr, conf["engine"]["max_len"])
    gen = traffic_lib.open_loop(tr, seed=ctx.seed, seconds=ctx.seconds,
                                vocab=conf["model"]["vocab_size"])
    warm = [(p, n) for _, p, n in gen["warm"]] or [
        (gen["window"][0][1][:8], 1)]
    eng, loop = setup(ctx, warm)

    t_open = time.perf_counter()
    setup_s = t_open - ctx.t0
    ctx.compiles.mark()
    due, failed, first, traced = window(ctx, loop, gen["window"], t_open,
                                        ctx.seconds)
    compiles_in_window = ctx.compiles.since_mark
    memory_peak = harness.memory_peak_bytes(ctx.cell["chips"])
    e2e, counters = stats(ctx, loop, due, first, traced, t_open,
                          ctx.seconds)
    counters["compiles_in_window"] = compiles_in_window
    st = counters["step_ms"]
    slow = sorted(range(len(st)), key=lambda k: -st[k])[:8]
    print("serve: slowest window steps (ms, admitted, completed): "
          + ", ".join(f"{st[k]:.0f}/{loop.step_events[first + k][0]}/"
                      f"{loop.step_events[first + k][1]}" for k in slow),
          file=sys.stderr, flush=True)
    print(f"serve: window {ctx.seconds} s, {len(due)} requests due, "
          f"{counters['tokens']} tokens, {len(st)} steps (median "
          f"{np.median(st) if st else 0:.1f} ms), lanes "
          f"{counters['lanes_valid']}/{counters['lanes_total']}, "
          f"{counters['queued_at_close']} queued at close, itl p99 "
          f"{pct(counters['itl_ms'], 99)} ms, set-up "
          f"{setup_s:.2f} s", file=sys.stderr, flush=True)

    # -- correctness, after the window, with the engine freed ---------------
    m = conf["model"]
    finished = [r for r in loop.reqs.values() if r.tokens is not None]
    malformed = sum(1 for r in finished
                    if r.tokens.shape != (r.max_new,)
                    or r.tokens.min() < 0
                    or r.tokens.max() >= m["vocab_size"])
    eng.close()
    del eng, loop.eng
    gc.collect()
    import jax
    jax.clear_caches()
    t_ref = time.perf_counter()
    gap, n_tok, control_gap = reference_gap(ctx, finished)
    print(f"serve: reference over {n_tok} served tokens of "
          f"{len(finished)} finished requests took "
          f"{time.perf_counter() - t_ref:.2f} s", file=sys.stderr,
          flush=True)
    cc = conf["correctness"]
    checks = compare(cc, gap, malformed, compiles_in_window)
    if control_gap is not None:
        # the control's first-ranked tokens in place of the served ones,
        # through the same comparison: it has to come out not correct
        counters["control_logit_gap_max"] = control_gap
        counters["control_correct"] = all(
            c["ok"] for c in compare(cc, control_gap, malformed,
                                     compiles_in_window))
    return {"e2e": e2e, "counters": counters, "checks": checks,
            "attempted": len(due), "failed": failed,
            "memory_peak_bytes": memory_peak, "setup_s": setup_s,
            "compiles_in_window": compiles_in_window}


def compare(cc, gap, malformed, compiles_in_window):
    """The checks of one run: the served tokens' widest logit gap against
    the configuration's limit; malformed outputs and compiles in the
    window, exactly none."""
    return [
        {"name": "served_logit_gap_max", "value": gap,
         "limit": cc["logit_gap_limit"], "rule": "value <= limit",
         "ok": gap is not None and gap <= cc["logit_gap_limit"]},
        {"name": "malformed_outputs", "value": malformed, "limit": 0,
         "rule": "value <= limit", "ok": malformed == 0},
        {"name": "compiles_in_window", "value": compiles_in_window,
         "limit": 0, "rule": "value <= limit",
         "ok": compiles_in_window == 0},
    ]


def pick_sample(finished, seed: int, want_tokens: int, most: int):
    """Finished requests for the check, drawn from the seed: the one with
    the most served tokens, then others in a seeded order until
    ``want_tokens`` served tokens or ``most`` requests."""
    if not finished:
        return []
    pool = sorted(finished, key=lambda r: r.uid)
    first = max(pool, key=lambda r: (len(r.tokens), -r.uid))
    rest = [r for r in pool if r is not first]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [first], len(first.tokens)
    for k in order:
        if n >= want_tokens or len(out) >= most:
            break
        out.append(rest[k])
        n += len(rest[k].tokens)
    return out


def reference_inputs(sample):
    """Token sequences and, per sequence, the positions whose logits
    choose the served tokens (the prompt's last position onwards)."""
    seqs, want, served = [], [], []
    for r in sample:
        P = len(r.prompt)
        seqs.append(np.concatenate([r.prompt, r.tokens[:-1]]))
        want.append(np.arange(P - 1, P - 1 + len(r.tokens)))
        served.append(r.tokens)
    return seqs, want, np.concatenate(served)


def gap_of(logits: np.ndarray, chosen: np.ndarray) -> float:
    """Widest gap by which the chosen token's logit lies below the best."""
    best = logits.max(axis=-1)
    got = np.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return float(np.max(best - got))


def reference_gap(ctx, finished):
    """(gap, served tokens compared, control gap or None). With
    ``ctx.control`` the int8 control also runs on the same sample: its
    first-ranked token at each position is judged by the float32
    reference's logits, as a served token would be."""
    import jax.numpy as jnp
    from chipbench.reference import lm as ref
    cc = ctx.config["correctness"]
    sample = pick_sample(finished, ctx.seed, cc["sample_tokens"],
                         cc["sample_requests"])
    if not sample:
        return None, 0, None
    seqs, want, served = reference_inputs(sample)
    args = (ctx.seed, ctx.config["model"],
            jnp.dtype(ctx.config["engine"]["param_dtype"]), seqs, want)
    rows = cc.get("rows", 4)
    logits = ref.logits_at(*args, rows=rows)
    control = None
    if ctx.control:
        low = ref.logits_at(*args, rows=rows, quant="int8")
        control = gap_of(logits, low.argmax(-1))
    return gap_of(logits, served), len(served), control
