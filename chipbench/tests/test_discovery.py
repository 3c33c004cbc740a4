"""A new cell is files and entries only: a new traffic file, a new
per-layer reader and a new entry in a copy of BENCHMARK.json are found
by name, with every file that was already there left as it is."""
import hashlib
import json
import os
import shutil

from chipbench import harness, traffic


def _digest(root):
    out = {}
    for dirpath, _, names in os.walk(os.path.join(root, "chipbench")):
        for n in names:
            if n.endswith((".py", ".json")):
                p = os.path.join(dirpath, n)
                with open(p, "rb") as f:
                    out[os.path.relpath(p, root)] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def test_new_traffic_cell_and_reader_need_no_edit(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    before = _digest(root)

    mix = {"kind": "open_loop", "rate_rps": 0.25, "warm_requests": 4,
           "prompt": {"dist": "lognormal", "median": 2048, "sigma": 0.3,
                      "min": 1024, "max": 3000},
           "output": {"dist": "lognormal", "median": 64, "sigma": 0.5,
                      "min": 16, "max": 128}}
    with open(os.path.join(root, "chipbench", "traffic",
                           "long_context.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "chipbench", "metrics",
                           "steps_in_window.decode.py"), "w") as f:
        f.write("def read(run):\n    return run['counters']['steps']\n")
    bench = harness.load_bench(root)
    conf_name = bench["configs"][0]["name"]
    bench["workloads"].append({
        "name": "new-long", "config": conf_name,
        "traffic": "long_context", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "steps_in_window.decode", "unit": "count",
        "better": "higher", "source": "program_counter", "layer": "device",
        "moves": "setup_s", "workloads": ["new-long"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell, conf, tr = harness.resolve_cell(bench, "new-long", root)
    assert tr == mix and conf["name"] == conf_name
    e2e, layer = harness.metrics_for(bench, "new-long")
    assert [m["name"] for m in layer] == ["steps_in_window.decode"]
    assert "setup_s" in [m["name"] for m in e2e]
    reader = harness.load_reader("steps_in_window.decode", root)
    assert reader.read({"counters": {"steps": 7}}) == 7
    gen = traffic.open_loop(tr, seed=2 ** 31 + 9, seconds=40,
                            vocab=conf["model"]["vocab_size"])
    assert len(gen["window"]) == 10 and len(gen["warm"]) == 4
    assert all(1024 <= len(p) <= 3000 for _, p, _ in gen["window"])
    assert _digest(root) == {**before, **{k: v for k, v in _digest(
        root).items() if k not in before}}


def test_seeds_change_tokens_not_work():
    tr = harness.load_json(os.path.join(harness.ROOT, "chipbench",
                                        "traffic", "chat_decode.json"))
    a = traffic.open_loop(tr, seed=3, seconds=30, vocab=1000)
    b = traffic.open_loop(tr, seed=2 ** 31 + 77, seconds=30, vocab=1000)
    sizes = lambda g: sorted((len(p), n) for _, p, n in g["window"])
    assert sizes(a) == sizes(b)
    assert sorted(len(p) for _, p, _ in a["warm"]) == \
        sorted(len(p) for _, p, _ in b["warm"])
    dues = [d for d, _, _ in a["window"]]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 30
    assert [d for d, _, _ in b["window"]] == dues
    assert any((p != q).any() for (_, p, _), (_, q, _) in
               zip(a["window"], b["window"]))
