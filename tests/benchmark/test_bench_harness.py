"""The harness finds its pieces by name, generates the same traffic from the
same seed, times the open loop from the due time, and refuses to run
without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import cell as bcell
from bench import client, measure, traffic

ROOT = bcell.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_pieces_are_found_by_name(name):
    c = bcell.load_cell(name)
    assert bcell.module("schemas", c["config"]["schema"]).query
    assert bcell.module("drivers", c["traffic"]["driver"]).run
    assert bcell.module("checks", c["config"]["check"]["kind"]).check
    reported = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2 and c["per_layer"]
    for m in c["end_to_end"] + c["per_layer"]:
        assert bcell.module("metrics", m["name"]).read
        if "moves" in m:
            assert m["moves"] in reported


def test_every_metric_and_config_has_its_file():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]


def _graph_stats(g):
    return (g.n, list(g.edges), np.asarray(g.log2_card).tolist(),
            np.asarray(g.log2_sel).tolist())


@pytest.mark.parametrize("n,seed", [(10, 0), (16, 7), (24, 3), (56, 256)])
def test_copied_musicbrainz_walk_matches_the_program(n, seed):
    from repro.workloads import generators as gen
    cfg = bcell.load_cell("mb-mid-open")["config"]
    q = bcell.module("schemas", "musicbrainz").query(cfg, n, seed)
    assert _graph_stats(client.to_graph(q)) == \
        _graph_stats(gen.musicbrainz_query(n, seed=seed))


@pytest.mark.parametrize("n,seed", [(30, 1), (100, 100), (150, 9)])
def test_copied_snowflake_matches_the_program(n, seed):
    from repro.workloads import generators as gen
    cfg = bcell.load_cell("snow-uniondp-closed")["config"]
    q = bcell.module("schemas", "snowflake").query(cfg, n, seed)
    assert _graph_stats(client.to_graph(q)) == \
        _graph_stats(gen.snowflake(n, seed=seed))


@pytest.mark.parametrize("name", CELLS)
def test_traffic_is_the_seeds_and_every_seed_gets_the_same_work(name):
    c = bcell.load_cell(name)
    c["traffic"]["max_requests"] = min(c["traffic"].get("max_requests", 4),
                                       c["traffic"].get("pool", 4))
    big = 2 ** 31 + 12345
    a, b = traffic.build(c, big, 10), traffic.build(c, big, 10)
    assert a == b
    other = traffic.build(c, 17, 10)
    sizes = lambda p: sorted(q["n"] for r in p["requests"]
                             for q in r["queries"])
    graphs = lambda p: sorted(json.dumps(q["edges"]) for r in p["requests"]
                              for q in r["queries"])
    assert sizes(a) == sizes(other)
    assert graphs(a) == graphs(other)
    assert [r["due"] is None for r in a["requests"]] == \
        [r["due"] is None for r in other["requests"]]
    if c["traffic"]["loop"] == "open":
        gaps = lambda p: sorted(np.diff(sorted(r["due"] for r in
                                               p["requests"])).round(9))
        assert len(a["requests"]) == round(c["traffic"]["rate_per_s"] * 10)
        assert sorted(r["tenant"] for r in a["requests"]) == \
            sorted(r["tenant"] for r in other["requests"])
        assert a["requests"] != other["requests"]
        assert len(gaps(a)) == len(gaps(other))
    if c["traffic"].get("order") == "catalogue":
        timing = lambda p: [(r["due"], r["tenant"], [q["n"] for q in r["queries"]],
                             [q["edges"] for q in r["queries"]])
                            for r in p["requests"]]
        assert timing(a) == timing(other)
    if c["traffic"].get("statistics") == "catalogue":
        queries = lambda p: sorted(json.dumps(q, sort_keys=True)
                                   for r in p["requests"] for q in r["queries"])
        assert queries(a) == queries(other)
        assert a["warmup"] == other["warmup"]
        assert a["requests"] != other["requests"]
    window = {json.dumps(q, sort_keys=True) for r in a["requests"]
              for q in r["queries"]}
    assert not window & {json.dumps(q, sort_keys=True)
                         for r in a["warmup"] for q in r["queries"]}


class _SlowSender:
    """Answers one request at a time, 0.1 s each."""

    def send(self, req, graphs, t0):
        rec = {"id": req["id"], "due": req["due"], "queries": 1,
               "send": time.perf_counter() - t0, "status": "ok"}
        time.sleep(0.1)
        rec["reply"] = time.perf_counter() - t0
        return rec


def test_open_loop_times_from_the_due_time_and_reports_lateness():
    reqs = [{"id": i, "due": 0.02 * i} for i in range(3)]
    t0 = time.perf_counter()
    recs = client.run_open(_SlowSender(), reqs, [[], [], []], t0, workers=1)
    late = [r["send"] - r["due"] for r in recs]
    assert late[0] < 0.05 and late[1] > 0.05 and late[2] > 0.15
    run = {"records": recs, "seconds": 0.1}
    lat = measure.latencies(run)
    assert lat == sorted(r["reply"] - r["due"] for r in recs)
    assert lat[-1] > 0.25                      # the queue's wait counts
    assert measure.window_s(run) == max(r["reply"] for r in recs)


def test_closed_loop_sends_after_each_reply_until_time_is_up():
    reqs = [{"id": i, "client": i % 2, "due": None} for i in range(40)]
    t0 = time.perf_counter()
    recs = client.run_closed(_SlowSender(), reqs, [[]] * 40, t0, 0.25)
    for c in (0, 1):
        mine = sorted((r for r in recs if r["id"] % 2 == c),
                      key=lambda r: r["send"])
        assert 2 <= len(mine) <= 4
        assert all(b["send"] >= a["reply"] for a, b in zip(mine, mine[1:]))


def test_percentile_matches_numpy():
    xs = sorted(np.random.default_rng(0).random(101).tolist())
    for p in (50, 95):
        assert measure.percentile(xs, p) == pytest.approx(np.percentile(xs, p))


def _run(cmd, cwd, env):
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_without_a_tpu_the_command_prints_no_result_and_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run([sys.executable, "bench/run.py", "--workload", "mb-mid-open",
              "--seed", "1", "--seconds", "1", "--trace", "0"], ROOT, env)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_the_benchmark_files_alone_do_not_run(tmp_path):
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run([sys.executable, *BENCH["command"][1:], "--workload",
              "mb-mid-open", "--seed", "1", "--seconds", "1"], tmp_path, env)
    assert p.returncode != 0
    assert "{" not in p.stdout
