"""A new configuration, cell, traffic kind and per-layer metric are added by
adding files (and entries to BENCHMARK.json), and the harness finds them."""
import json
import shutil
import time

import harness

DRIVER = '''
import jax.numpy as jnp


class Driver:
    def __init__(self, cell):
        self.cell, self.calls, self.attempted, self.failed = cell, 0, 0, 0

    def setup(self):
        self.x = jnp.arange(self.cell.config["size"], dtype=jnp.float32)

    def window(self, seconds):
        for _ in range(self.cell.traffic["calls"]):
            self.total = float((self.x * 2).sum())
            self.calls += 1
        self.attempted = self.calls

    def release(self):
        self.x = None

    def check(self):
        n = self.cell.config["size"]
        return {"sum_error": abs(self.total - n * (n - 1))}

    def end_to_end(self):
        return {"toy_calls": float(self.calls)}

    def counters(self):
        return {"calls": self.calls}
'''

METRIC = '''
def read(ctx):
    calls = ctx["counters"].get("calls")
    return float(calls) if calls else None
'''


def test_harness_finds_added_files(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    b = tmp_path / "bench"
    (b / "traffic" / "toy_kind.py").write_text(DRIVER)
    (b / "traffic" / "toy_mix.json").write_text(json.dumps(
        {"kind": "toy_kind", "calls": 3}))
    (b / "configs" / "toy.json").write_text(json.dumps({"size": 100}))
    (b / "workloads" / "toy.cell.json").write_text(json.dumps(
        {"config": "toy", "traffic": "toy_mix", "chips": 1, "why": "test",
         "limits": {"sum_error": 0.0}}))
    (b / "metrics" / "toy_calls_made.py").write_text(METRIC)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "test",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "toy.cell", "config": "toy",
                              "traffic": "toy_mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "toy_calls", "unit": "calls",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["toy.cell"]})
    spec["per_layer"].append({"name": "toy_calls_made", "unit": "calls",
                              "better": "higher", "source": "program_counter",
                              "layer": "toy", "moves": "toy_calls",
                              "workloads": ["toy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    import jax
    assert harness.load_cell("toy.cell", root=tmp_path).per_layer[-1][
        "name"] == "toy_calls_made"
    cell = harness.load_cell("toy.cell", root=tmp_path)
    cell.devices = jax.devices()[:1]
    plain = harness.run_cell(cell, 7, 0.1, False, t_start=time.perf_counter())
    assert plain["correct"]
    assert set(plain["metrics"]) == {"setup_s", "toy_calls"}
    assert plain["metrics"]["toy_calls"] == {"value": 3.0, "unit": "calls"}
    traced = harness.run_cell(harness.load_cell("toy.cell", root=tmp_path),
                              7, 0.1, True, t_start=time.perf_counter())
    assert traced["metrics"] == {"toy_calls_made": {"value": 3.0,
                                                    "unit": "calls"}}
    assert "breakdown" in traced and "window_s" in traced["device"]
    # the real cells are untouched by the additions
    assert harness.load_cell("fit.tmkt", root=tmp_path).per_layer == \
        harness.load_cell("fit.tmkt").per_layer


def test_result_line_format(capsys):
    result = {"correct": True, "attempted": 3, "failed": 0,
              "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1, "memory_peak_bytes": 5},
              "checks": {"split_regret": {"value": 1e-7, "limit": 1e-4}},
              "_in_window": {"trace_s": 0.5, "cache_misses": 0}}
    harness.print_result(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert err.strip().splitlines()[-1] == \
        "check split_regret: 1e-07 (limit 0.0001)"
