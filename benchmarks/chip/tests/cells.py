"""Small versions of the benchmark's cells, for runs on the CPU."""
from benchmarks.chip import run

#: sizes that keep every path (carry mode needs d > 128, so the silo cells
#: keep a wide d) and finish in seconds on a CPU
SMALL = {
    "ha.full": ({"m": 4, "d": 160, "n_min": 24, "n_max": 40},
                {"rounds": 12, "trace_seconds": 1}),
    "xdev.blocks": ({"m": 2000, "d": 24, "n_min": 20, "n_max": 40,
                     "n_pad": 40, "cohort": 8, "cache_clients": 30},
                    {"blocks": 12, "trace_seconds": 1}),
    "xdev.serve": ({"m": 2000, "d": 24, "n_min": 20, "n_max": 40,
                    "n_pad": 40, "cohort": 8, "cache_clients": 30},
                   {"train_blocks": 24, "rate_per_s": 100,
                    "trace_seconds": 1}),
}


#: cells whose harness is here but which ``BENCHMARK.json`` does not list
#: yet (PERF.md, Open questions)
UNLISTED = {"xdev.serve": {"name": "xdev.serve", "config": "har_xdev_100k",
                           "traffic": "serve_zipf", "chips": 1}}


def benchmark():
    bench = run.load_benchmark()
    listed = {w["name"] for w in bench["workloads"]}
    bench["workloads"] += [w for n, w in UNLISTED.items() if n not in listed]
    return bench


def small_cell(name: str, seed: int = 2**31 + 5, seconds: float = 1.0):
    bench = benchmark()
    cell = run.make_cell(bench, name, seed, seconds, False)
    cfg, traffic = SMALL[name]
    cell.config.update(cfg)
    cell.traffic.update(traffic)
    return bench, cell


def small_run(name: str, **kw):
    bench, cell = small_cell(name, **kw)
    return run.run_cell(cell, require_chip=False, bench=bench)
