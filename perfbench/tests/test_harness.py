"""CPU tests of the benchmark harness: seeded inputs, the loops'
accounting on a fake clock, the check against the reference and its
controls, faults planted in the served path, and the refusal to run off
the chip."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import corpus  # noqa: E402
import harness  # noqa: E402
import loops  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

POISSON = harness.load_module("arrivals", "poisson")

SPEC = harness.load_json(HERE.parent / "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]
BIG_SEED = 2**31 + 12345


def tiny(name: str) -> tuple[dict, dict]:
    """The cell's configuration and mix at a size the CPU runs in
    seconds."""
    _, cfg, mix = harness.cell_spec(SPEC, name)
    cfg = dict(cfg, docs=160, vocab=1500)
    mix = dict(mix, warmup=[dict(p, seconds=0.5) if "seconds" in p else p
                            for p in mix["warmup"]])
    if "rate_qps" in mix:
        mix["rate_qps"] = 6.0
    return cfg, mix


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_collection_and_queries(name):
    cfg, mix = tiny(name)
    a, na = corpus.make_collection(cfg, BIG_SEED)
    b, nb = corpus.make_collection(cfg, BIG_SEED)
    c, _ = corpus.make_collection(cfg, BIG_SEED + 1)
    assert na == nb == cfg["docs"]
    assert len(a) == len(b) and all(np.array_equal(x, y)
                                    for x, y in zip(a, b))
    assert len(a) != len(c) or any(not np.array_equal(x, y)
                                   for x, y in zip(a, c))
    qa = traffic.QueryStream(mix, a, BIG_SEED, 0).take(60)
    qb = traffic.QueryStream(mix, b, BIG_SEED, 0).take(60)
    assert qa == qb
    assert qa != traffic.QueryStream(mix, a, BIG_SEED, 1).take(60)
    for q in qa:
        assert q == sorted(set(q)) and all(0 <= t < len(a) for t in q)


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_same_lengths_and_gaps(name):
    cfg, mix = tiny(name)
    lists, _ = corpus.make_collection(cfg, 3)
    lens = [sorted(len(q) for q in traffic.QueryStream(mix, lists, s, 0)
                   .take(2 * traffic.BLOCK)) for s in (3, BIG_SEED)]
    assert lens[0] == lens[1]
    g1 = POISSON.arrival_gaps(20.0, 300, 3)
    g2 = POISSON.arrival_gaps(20.0, 300, BIG_SEED)
    assert not np.array_equal(g1, g2)
    np.testing.assert_array_equal(np.sort(g1), np.sort(g2))
    assert len(POISSON.open_schedule({"rate_qps": 20}, 30.0, 3)) == \
        len(POISSON.open_schedule({"rate_qps": 20}, 30.0, BIG_SEED)) == 600


def test_same_terms_every_seed_gives_every_seed_the_same_ranks():
    """Every seed serves the same collection and, block by block, the
    same queries, in another order."""
    for name in CELLS:
        cfg, mix = tiny(name)
        lists, _ = harness.collection(cfg)
        got = {}
        for s in (3, BIG_SEED):
            qs = traffic.QueryStream(mix, lists, s, 0).take(2 * traffic.BLOCK)
            got[s] = qs, [sorted(qs[:traffic.BLOCK]),
                          sorted(qs[traffic.BLOCK:])]
        assert got[3][0] != got[BIG_SEED][0]
        assert got[3][1] == got[BIG_SEED][1]


@pytest.mark.parametrize("name", CELLS)
def test_queries_come_from_one_document_without_stopwords(name):
    cfg, mix = tiny(name)
    lists, _ = harness.collection(cfg)
    stop = set(traffic.stopword_terms(lists, mix["stopwords"]).tolist())
    assert len(stop) == mix["stopwords"]
    for q in traffic.QueryStream(mix, lists, BIG_SEED, 0).take(100):
        assert not stop & set(q)
        assert reference.and_reference(lists, q).size > 0


@pytest.mark.parametrize("name", CELLS)
def test_kind_and_arrivals_are_modules_found_by_name(name):
    _, _, mix = harness.cell_spec(SPEC, name)
    kind = harness.load_module("kinds", mix["kind"])
    arrivals = harness.load_module("arrivals", mix["arrivals"])
    assert all(callable(getattr(kind, f)) for f in ("submit", "prime",
                                                     "check"))
    assert all(callable(getattr(arrivals, f)) for f in ("run", "finish",
                                                         "attempted"))
    assert isinstance(arrivals.OPEN, bool)
    with pytest.raises(KeyError):
        harness.load_module("arrivals", "no-such-process")


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeServer:
    """Answers each query after ``ticks`` ticks of ``tick_s`` seconds;
    queries listed in ``stall`` are never answered."""

    def __init__(self, clock, ticks=2, tick_s=0.01, stall=()):
        self.clock, self.ticks, self.tick_s = clock, ticks, tick_s
        self.stall = set(stall)
        self.left, self.done, self.n = {}, {}, 0

    def submit(self, q):
        self.n += 1
        if tuple(q) not in self.stall:
            self.left[self.n] = (self.ticks, q)
        return self.n

    def tick(self):
        self.clock.t += self.tick_s
        for qid, (k, q) in list(self.left.items()):
            if k <= 1:
                del self.left[qid]
                self.done[qid] = q
            else:
                self.left[qid] = (k - 1, q)
        return len(self.left)

    def poll(self, qid):
        return self.done.pop(qid, None)


def test_open_loop_counts_unfinished_queries_at_their_age():
    clock = FakeClock()
    srv = FakeServer(clock, ticks=2, tick_s=0.01, stall=[(7,)])
    d = loops.Driver(srv, clock, clock.sleep)
    queries = [[i] for i in range(10)]
    due = [0.1 * i for i in range(10)]
    recs = loops.open_loop(d, queries, due, 1.0)
    t_end = d.stats.end
    lat = loops.latency_at_close(recs, t_end)
    assert len(lat) == 10
    stalled = recs[7]
    assert stalled.done is None
    assert lat[7] == pytest.approx(t_end - stalled.due)
    for i, r in enumerate(recs):
        if i != 7:
            assert r.done is not None
            assert lat[i] == pytest.approx(r.done - r.due)
            assert 0.02 <= lat[i] < 0.05
    assert loops.completed_in_window(recs, t_end) == 9
    loops.finish_open(d, recs, 0.5)
    assert recs[7].answer is None and d.outstanding


def test_open_loop_times_from_due_not_from_send():
    clock = FakeClock()
    srv = FakeServer(clock, ticks=1, tick_s=0.3)
    d = loops.Driver(srv, clock, clock.sleep)
    recs = loops.open_loop(d, [[1], [2], [3]], [0.0, 0.05, 0.1], 2.0)
    # the second and third came due during the first tick: they are sent
    # late, and the wait counts in their latency
    assert recs[1].sent - recs[1].due > 0.2
    lat = loops.latency_at_close(recs, d.stats.end)
    assert lat[1] == pytest.approx(recs[1].done - recs[1].due)
    assert max(d.stats.lag_s) > 0.2


def test_closed_loop_keeps_clients_busy():
    clock = FakeClock()
    srv = FakeServer(clock, ticks=3, tick_s=0.01)
    d = loops.Driver(srv, clock, clock.sleep)
    n = iter(range(10**6))
    recs = loops.closed_loop(d, lambda: [next(n)], 4, 1.0)
    done = loops.completed_in_window(recs, d.stats.end)
    assert 4 * 30 <= done <= 4 * 34
    assert len(recs) - done <= 4
    # a replay runs on past the window by what ``extra`` reads
    d2 = loops.Driver(FakeServer(clock, ticks=3, tick_s=0.01), clock,
                      clock.sleep)
    longer = loops.closed_loop(d2, lambda: [next(n)], 4, 1.0,
                               extra=lambda: 0.5)
    assert len(longer) >= 1.4 * len(recs)


def answered(lists, queries, fn):
    return [loops.Record(q, 0.0, 0.0, 1.0, fn(q)) for q in queries]


def test_and_check_flags_a_corrupted_answer_and_the_control():
    cfg, mix = tiny("gov2-web.and")
    lists, n = harness.collection(cfg)
    qs = traffic.QueryStream(mix, lists, 5, 0).take(40)
    recs = answered(lists, qs, lambda q: reference.and_reference(lists, q))
    ok = harness.check(cfg, mix, lists, n, recs)
    assert harness.passes(ok) and ok["wrong_answers"]["value"] == 0
    victim = next(r for r in recs if r.answer.size)
    victim.answer = victim.answer[1:]
    bad = harness.check(cfg, mix, lists, n, recs)
    assert bad["wrong_answers"]["value"] == 1 and not harness.passes(bad)
    ctl = harness.check(cfg, mix, lists, n, recs, control=True)
    assert ctl["wrong_answers"]["value"] > 0 and not harness.passes(ctl)
    recs[0].answer = None
    assert harness.check(cfg, mix, lists, n, recs)["unanswered"]["value"] \
        == 1


def program_topk(lists, n, g, q, k):
    """BM25 top-k as the program states it: float32 idf and doc weights,
    a float32 sum in ascending term order, one float32 product."""
    bm = reference.BM25(lists, n, g["bm25_k1"], g["bm25_b"])
    docs, _ = bm.scores(q)
    acc = np.zeros(docs.size, np.float32)
    for t in sorted(q):
        hit = np.isin(docs, bm.lists[t])
        acc = acc + np.where(hit, np.float32(bm.idf[t]), np.float32(0))
    s = (bm.w[docs].astype(np.float32) * acc).astype(np.float32)
    order = np.lexsort((docs, -s.astype(np.float64)))[:k]
    return loops.Record(q, 0.0, 0.0, 1.0, type("R", (), {
        "docs": docs[order], "scores": s[order]})())


def test_topk_check_passes_float32_and_fails_the_bfloat16_control():
    cfg, mix = tiny("msmarco-passage.top10")
    g = cfg["guarantees"]
    lists, n = harness.collection(cfg)
    qs = traffic.QueryStream(mix, lists, 9, 0).take(40)
    recs = [program_topk(lists, n, g, q, mix["k"]) for q in qs]
    ok = harness.check(cfg, mix, lists, n, recs)
    assert harness.passes(ok)
    assert ok["score_gap"]["value"] < g["score_gap_limit"] / 10
    ctl = harness.check(cfg, mix, lists, n, recs, control=True)
    assert ctl["score_gap"]["value"] > 3 * g["score_gap_limit"]
    victim = recs[0].answer
    victim.docs = victim.docs.copy()
    victim.docs[-1] = (victim.docs[-1] + 1) % n
    assert not harness.passes(harness.check(cfg, mix, lists, n, recs))


def alter_probes(srv):
    """A fault: every probe value the device engine produces comes back
    off by one, where the engine produces it."""
    eng = srv.engine
    probe = eng.dispatch_round
    eng.dispatch_round = lambda *a, **k: np.asarray(probe(*a, **k)) + 1


def alter_decodes(srv):
    """A fault: every doc id a ranked page decode produces comes back off
    by one, where the engine produces it."""
    eng = srv.engine
    score = eng.dispatch_score_round
    eng.dispatch_score_round = (
        lambda *a, **k: np.asarray(score(*a, **k)) + 1)


FAULTS = {"gov2-web.and": alter_probes,
          "msmarco-passage.top10": alter_decodes}


@pytest.mark.parametrize("faulty", [False, True],
                         ids=["sound", "answer-altered"])
@pytest.mark.parametrize("name", CELLS)
def test_run_without_the_chip_check_for_fault(name, faulty):
    """A whole run, past the look for a chip, at a CPU size: correct when
    the served path is sound, not correct with a fault planted in it."""
    cfg, mix = tiny(name)
    out = harness.run_cell(SPEC, name, BIG_SEED, 1.5, False,
                           time.perf_counter(), require_tpu=False,
                           cfg=cfg, mix=mix,
                           after_server=FAULTS[name] if faulty else None,
                           compile_cache=False, log=open(os.devnull, "w"))
    assert out["attempted"] > 0
    assert out["correct"] is (not faulty), out["checks"]
    assert list(out)[-1] == "checks"
    want = {m["name"] for m in SPEC["end_to_end"]
            if harness.reports(m, name)}
    assert set(out["metrics"]) == want


def run_py(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELLS[0],
         "--seed", str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_to_run_off_the_chip():
    p = run_py(HERE.parent)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(HERE.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    p = run_py(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_benchmark_json_names_files_that_exist():
    spec = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    assert spec["command"][1].startswith(spec["paths"][0] + "/")
    for c in spec["configs"]:
        cfg = harness.load_json(HERE.parent / c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert all(k in cfg for k in c["reduced"] + list(corpus.CORPUS_KEYS))
        assert isinstance(cfg["collection_seed"], int)
    e2e = {m["name"] for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        harness.cell_spec(spec, w["name"])
        reported = {m["name"] for m in spec["end_to_end"]
                    if harness.reports(m, w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        layered = [m for m in spec["per_layer"]
                   if harness.layer_reports(m, w["name"], reported)]
        assert layered
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert callable(harness.load_module(
            "metrics", m["name"].partition(".")[0]).read)
