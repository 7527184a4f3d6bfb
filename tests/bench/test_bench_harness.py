"""The harness end to end on the CPU at a tiny size: the result line, the
chip check, per-layer readers found by name, and faults planted under the
timed path turning ``correct`` false."""
import json
import time

import numpy as np
import pytest

from bench_tiny import cell

from bench import harness, tracereduce

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture
def quick(monkeypatch):
    """No warm-up: the tiny programs compile in the window, which a CPU
    test does not time."""
    monkeypatch.setattr(harness, "warm_up", lambda *a, **k: None)


def _run(name, trace=False, seconds=1.0):
    return harness.run_cell(cell(name), 2**32 + 7, seconds, trace,
                            time.perf_counter())


@pytest.mark.parametrize("name", ["webtables_uniform.mc",
                                  "gittables_uniform.union"])
def test_result_line(quick, name):
    out = _run(name)
    assert list(out) == KEYS                 # checks come last
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"]
                                   for m in cell(name)["end_to_end"]}
    assert {"p50_ms", "p95_ms", "goodput_rps", "setup_s"} <= \
        set(out["metrics"])
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(c == {"value": 0, "limit": 0}
               for c in out["checks"].values())
    json.dumps(out)


def _plant(monkeypatch, fault):
    from repro.serve.engine import DiscoveryEngine

    orig = DiscoveryEngine.serve_many

    def serve_many(self, queries, **kw):
        return fault(orig, self, list(queries), **kw)

    monkeypatch.setattr(DiscoveryEngine, "serve_many", serve_many)


def _half_batch(orig, self, queries, **kw):
    """Half of each batch left out: its requests get the other half's
    answers."""
    out = orig(self, queries[:(len(queries) + 1) // 2], **kw)
    return out + out[:len(queries) - len(out)]


def _altered(orig, self, queries, **kw):
    """One answer altered where it is produced: the top table dropped."""
    out = orig(self, queries, **kw)
    out[0].table_ids = out[0].table_ids[1:] + [10**6]
    return out


@pytest.mark.parametrize("fault", [_half_batch, _altered])
def test_planted_fault_is_not_correct(quick, monkeypatch, fault):
    _plant(monkeypatch, fault)
    out = harness.run_cell(cell("gittables_uniform.union"), 5, 1.0, False,
                           time.perf_counter())
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_no_chip_exits_without_a_result(capsys, monkeypatch, tmp_path):
    monkeypatch.setenv("TPU_LOG_DIR", str(tmp_path))
    rc = harness.main(["--workload", "gittables_uniform.union",
                       "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", ["webtables_uniform.mc",
                                  "gittables_uniform.union"])
def test_control_run_is_not_correct(quick, name):
    """The control in the program's place, driven and judged by the
    harness as a run is: ``correct`` comes out false."""
    from bench import control

    out = control.run(cell(name), 2**31 + 3, 1.0, cap=2)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0
    assert out["attempted"] > 0


class _Counter:
    """A compile counter whose program count rises inside the first
    ``dirty`` windows the harness drives."""

    def __init__(self, dirty):
        self.dirty, self.n, self.windows = dirty, 0, 0

    def snapshot(self):
        return (self.n, 0, 0)


def test_a_window_that_compiles_is_driven_again(quick, monkeypatch):
    from bench.traffic import loadgen

    counter = _Counter(dirty=1)
    monkeypatch.setattr(harness, "CompileCounter", lambda: counter)
    drive = loadgen.drive_closed

    def drive_closed(*a, **k):
        counter.windows += 1
        if counter.windows <= counter.dirty:
            counter.n += 1
        return drive(*a, **k)

    monkeypatch.setattr(loadgen, "drive_closed", drive_closed)
    out = harness.run_cell(cell("gittables_uniform.union"), 6, 0.5, False,
                           time.perf_counter())
    assert counter.windows == 2
    assert out["correct"] is True
    assert out["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}


def test_a_window_that_always_compiles_is_not_correct(quick, monkeypatch):
    from bench.traffic import loadgen

    counter = _Counter(dirty=10**6)
    monkeypatch.setattr(harness, "CompileCounter", lambda: counter)
    monkeypatch.setattr(harness, "RUN_LIMIT_S", 0.0)
    drive = loadgen.drive_closed

    def drive_closed(*a, **k):
        counter.windows += 1
        counter.n += 1
        return drive(*a, **k)

    monkeypatch.setattr(loadgen, "drive_closed", drive_closed)
    out = harness.run_cell(cell("gittables_uniform.union"), 6, 0.5, False,
                           time.perf_counter())
    assert counter.windows == 1
    assert out["correct"] is False
    assert out["checks"]["compiles_in_window"]["value"] == 1


def test_ladder_walk_sends_every_batch_size_at_both_ends():
    """Features are counted from the lake; the walk sends each batch size
    along each ordering, from the smallest queries to the largest."""
    from concurrent.futures import Future

    import numpy as np

    from bench import lakegen

    c = cell("webtables_uniform.mc")
    lake = lakegen.generate(c["config"], 4)
    pool = harness.warm_pool(c["traffic"], lake, 4)
    feats = harness.query_features(pool, lake)
    q = pool[0]
    mc, sc = q[2][0], q[2][1]
    assert feats[0, 0] == len(set(mc[2])) + len(set(sc[2]))
    assert feats[0, 2] == 2
    counts = np.bincount(lake.cat, minlength=lake.vocab)
    assert feats[0, 1] == counts[[v for t in mc[2] for v in t]].max()

    class Server:
        def __init__(self):
            self.got = []

        def submit(self, key, **_):
            self.got.append(key)
            f = Future()
            f.set_result(None)
            return f

    srv = Server()
    sent = harness.ladder_walk(srv, list(range(len(pool))), feats,
                               np.random.default_rng(0))
    per_order = sum(len(np.unique(np.linspace(0, len(pool) - b,
                                              harness.WALK_PLACES)
                                  .astype(int))) * b
                    for b in range(1, harness.MAX_BATCH + 1))
    assert sent == len(srv.got) == 3 * per_order
    widest = int(np.argmax(feats[:, 0]))
    assert widest in srv.got and int(np.argmin(feats[:, 0])) in srv.got


def test_metric_added_as_a_file_is_found_by_name(tmp_path):
    (tmp_path / "requests_read.py").write_text(
        "def read(ctx):\n    return float(len(ctx.requests))\n")
    mod = harness.load_metric("requests_read", tmp_path)
    assert mod.read(type("C", (), {"requests": [1, 2, 3]})) == 3.0


def test_every_benchmark_metric_has_a_reader():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(harness.load_metric(m["name"]).read)


def test_unknown_device_kind_has_no_peaks():
    assert tracereduce.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        tracereduce.peaks("cpu")


def test_percentile_counts_failures_as_slowest():
    assert harness.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    assert harness.percentile([1.0] * 19 + [float("inf")], 95) == 1.0
    assert harness.percentile([1.0] * 18 + [float("inf")] * 2, 95) \
        == float("inf")
    assert np.isinf(harness.percentile([], 50))
