"""reprolint: one positive + one negative fixture per rule ID, planted
violations per family, baseline round-trip, and the tier-1 repo-clean
gate (the whole tree must lint to zero non-baselined findings).

Fixtures are written into tmp repo trees (rule scoping is path-pattern
based relative to a passed root), so the checks exercise exactly the
paths the real rules guard without touching the repo.
"""
import pathlib
import sys
import textwrap

import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tools.reprolint import baseline as baseline_mod  # noqa: E402
from tools.reprolint import graph, quickstart  # noqa: E402
from tools.reprolint.__main__ import main, run_paths  # noqa: E402
from tools.reprolint.rules import lint_file  # noqa: E402


def _lint(tmp_path, rel, source):
    """Write ``source`` at ``rel`` under a tmp repo root and lint it."""
    f = tmp_path / rel
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    return lint_file(tmp_path, f)


def _rules(findings):
    return sorted({f.rule for f in findings})


# -- D family ---------------------------------------------------------------

def test_d101_wall_clock_positive(tmp_path):
    out = _lint(tmp_path, "src/repro/core/foo.py", """\
        import time
        def f():
            return time.perf_counter()
        """)
    assert _rules(out) == ["D101"]
    assert out[0].context == "f"


def test_d101_wall_clock_negative(tmp_path):
    # the sanctioned module itself is exempt; aliased safe imports resolve
    assert _lint(tmp_path, "src/repro/utils/timing.py", """\
        import time
        def tick():
            return time.perf_counter()
        """) == []
    assert _lint(tmp_path, "src/repro/core/foo.py", """\
        from repro.utils.timing import tick
        def f():
            return tick()
        """) == []


def test_d102_stdlib_random_positive(tmp_path):
    out = _lint(tmp_path, "benchmarks/foo.py", """\
        import random
        def f():
            return random.random()
        """)
    assert _rules(out) == ["D102"]
    assert len(out) == 2          # the import AND the call


def test_d102_stdlib_random_negative(tmp_path):
    # jax.random is seeded/key-threaded -- resolving the alias keeps it legal
    assert _lint(tmp_path, "src/repro/core/foo.py", """\
        from jax import random
        def f(key):
            return random.split(key)
        """) == []


def test_d103_unseeded_rng_positive(tmp_path):
    out = _lint(tmp_path, "src/repro/data/foo.py", """\
        import numpy as np
        def f():
            g = np.random.default_rng()
            np.random.seed(0)
            return g
        """)
    assert _rules(out) == ["D103"]
    assert len(out) == 2          # unseeded default_rng + legacy global seed


def test_d103_unseeded_rng_negative(tmp_path):
    assert _lint(tmp_path, "src/repro/data/foo.py", """\
        import numpy as np
        def f(seed, tid):
            return np.random.default_rng(
                np.random.SeedSequence(seed, spawn_key=(tid,)))
        """) == []


def test_d104_bench_time_positive(tmp_path):
    out = _lint(tmp_path, "benchmarks/common.py", """\
        from datetime import datetime
        def provenance():
            return {"when": datetime.now().isoformat()}
        """)
    assert "D104" in _rules(out)


def test_d104_bench_time_negative(tmp_path):
    # same code outside the provenance-writing scope is not D104's business
    out = _lint(tmp_path, "src/repro/core/foo.py", """\
        from datetime import datetime
        def f():
            return datetime.now()
        """)
    assert "D104" not in _rules(out)


def test_d105_silent_fault_swallow_positive(tmp_path):
    out = _lint(tmp_path, "src/repro/cohort/foo.py", """\
        def f(block):
            try:
                return block()
            except Exception:
                pass
        def g(block):
            try:
                return block()
            except:
                return None
        """)
    assert _rules(out) == ["D105"]
    assert len(out) == 2          # the blanket pass AND the bare except


def test_d105_silent_fault_swallow_negative(tmp_path):
    # handled blanket catches (retry ladders that re-raise/record) are the
    # sanctioned shape; narrow excepts may pass; scope is src/repro only
    assert _lint(tmp_path, "src/repro/cohort/foo.py", """\
        def f(block, attempts):
            err = None
            for _ in range(attempts):
                try:
                    return block()
                except Exception as e:  # noqa: BLE001
                    err = e
            raise err
        def g(d, k):
            try:
                return d[k]
            except KeyError:
                pass
        """) == []
    assert _lint(tmp_path, "benchmarks/foo.py", """\
        def f(block):
            try:
                return block()
            except Exception:
                pass
        """) == []


def test_d106_obs_time_import_positive(tmp_path):
    # inside repro.obs even an (unused) stdlib `time` import is banned --
    # the package's wall clock comes only from repro.utils.timing
    out = _lint(tmp_path, "src/repro/obs/tracer.py", """\
        import time
        from time import perf_counter
        """)
    assert _rules(out) == ["D106"]
    assert len(out) == 2          # the import AND the from-import


def test_d106_obs_internal_reach_positive(tmp_path):
    out = _lint(tmp_path, "src/repro/cohort/foo.py", """\
        from repro.obs.tracer import Tracer
        from repro.obs import MetricsRegistry
        from repro import obs
        def f():
            return obs.tracer.Span("x", "main")
        """)
    assert _rules(out) == ["D106"]
    assert len(out) == 3          # two imports + the ad-hoc Span construction


def test_d106_negative(tmp_path):
    # the sanctioned surface: the facade factory/null object outside obs,
    # timing-routed clock reads inside obs, export helpers via the facade
    assert _lint(tmp_path, "src/repro/obs/tracer.py", """\
        from repro.utils.timing import tick
        def now():
            return tick()
        """) == []
    assert _lint(tmp_path, "src/repro/cohort/foo.py", """\
        from repro import obs
        def f(enabled):
            tel = obs.telemetry(enabled)
            with tel.span("pack", block=0):
                tel.counter("blocks_packed").inc()
            return obs.metrics_summary(tel)
        """) == []
    # tests and scripts outside the scoped trees are not D106's business
    assert _lint(tmp_path, "examples/foo.py", """\
        from repro.obs.tracer import Tracer
        """) == []


def test_d107_serve_rng_and_state_import_positive(tmp_path):
    out = _lint(tmp_path, "src/repro/serve/hot.py", """\
        import jax
        from repro.cohort.omega import ClusterOmega
        def sample(key):
            return jax.random.uniform(key, (4,))
        """)
    assert "D107" in _rules(out)
    d107 = [f for f in out if f.rule == "D107"]
    assert len(d107) == 2         # the omega import AND the RNG draw


def test_d107_serve_trace_write_positive(tmp_path):
    out = _lint(tmp_path, "src/repro/serve/hot.py", """\
        def f(trace, ids):
            trace.begin_round(ids)
            trace.charge(3)
        """)
    assert _rules(out) == ["D107"]
    assert len(out) == 2


def test_d107_negative(tmp_path):
    # the sanctioned shape: snapshots in, pure lookups out; driving the
    # training loop through its own API is the refresh loop's job
    assert _lint(tmp_path, "src/repro/serve/cold.py", """\
        import numpy as np
        from repro import obs
        from repro.serve.store import ServedSnapshot
        def weights(snap, ids):
            return snap.client_weights(np.asarray(ids))
        """) == []
    # the LM decode engine keeps its seeded sampling (exempt file)
    assert _lint(tmp_path, "src/repro/serve/engine.py", """\
        import jax
        def sample(key, logits):
            return jax.random.categorical(key, logits)
        """) == []
    # outside src/repro/serve D107 does not apply
    assert _lint(tmp_path, "src/repro/cohort/foo.py", """\
        from repro.cohort.omega import ClusterOmega
        """) == []


# -- P family ---------------------------------------------------------------

def test_p201_raw_gram_positive(tmp_path):
    out = _lint(tmp_path, "src/repro/kernels/sdca/foo.py", """\
        import jax.numpy as jnp
        def gram(Xc):
            a = Xc @ Xc.T
            b = jnp.matmul(Xc, Xc.T)
            return a + b
        """)
    assert _rules(out) == ["P201"]
    assert len(out) == 2


def test_p201_raw_gram_negative(tmp_path):
    # different bases (W @ C.T) are an ordinary product, not a self-Gram;
    # and the defining module (core/subproblem.py) is out of scope
    assert _lint(tmp_path, "src/repro/cohort/omega.py", """\
        def f(W_p, centroids):
            return W_p @ centroids.T
        """) == []
    assert _lint(tmp_path, "src/repro/core/subproblem.py", """\
        import jax.numpy as jnp
        def _chunk_gram(Xc):
            return jnp.matmul(Xc, Xc.T)
        """) == []


def test_p202_manual_reduction_positive(tmp_path):
    out = _lint(tmp_path, "src/repro/core/engine.py", """\
        import jax.numpy as jnp
        def rowdots(A, B):
            return jnp.sum(A * B, axis=1)
        """)
    assert _rules(out) == ["P202"]


def test_p202_manual_reduction_negative(tmp_path):
    # plain sums are fine, and attention kernels are not SDCA engine code
    assert _lint(tmp_path, "src/repro/core/engine.py", """\
        import jax.numpy as jnp
        def total(A):
            return jnp.sum(A, axis=1)
        """) == []
    assert _lint(tmp_path, "src/repro/kernels/flash_attention/foo.py", """\
        import jax.numpy as jnp
        def scores(q, k):
            return jnp.sum(q * k, axis=-1)
        """) == []


def test_p203_scan_host_materialization_positive(tmp_path):
    out = _lint(tmp_path, "src/repro/core/foo.py", """\
        import numpy as np
        def _round(carry, x):
            bad = float(x)
            worse = x.item()
            worst = np.asarray(x)
            return carry, bad + worse + worst
        class Engine:
            def scan_round_fn(self):
                return _round
        """)
    assert _rules(out) == ["P203"]
    assert len(out) == 3


def test_p203_scan_host_materialization_negative(tmp_path):
    # host pulls OUTSIDE the registered round fn are legal
    assert _lint(tmp_path, "src/repro/core/foo.py", """\
        def _round(carry, x):
            return carry, x * 2
        def after_scan(x):
            return float(x)
        class Engine:
            def scan_round_fn(self):
                return _round
        """) == []


# -- T family ---------------------------------------------------------------

_T_CLASS = """\
    class Loop:
        def __init__(self):
            self.sched = []  # owner: main
            self.buf = {}  # owner: pack
            self.trace = None  # owner: solve

        def pack(self, b):  # worker: pack
            self.buf[b] = b
            return %s

        def fold(self, b):%s
            self.sched.append(b)
    """


def test_t301_wrong_worker_positive(tmp_path):
    out = _lint(tmp_path, "src/repro/cohort/driver.py",
                _T_CLASS % ("self.sched[b]", "  # worker: main"))
    assert _rules(out) == ["T301"]
    assert "owned by main" in out[0].message
    assert out[0].context == "Loop.pack"


def test_t301_wrong_worker_negative_and_suppression(tmp_path):
    # own-worker access is clean
    assert _lint(tmp_path, "src/repro/cohort/driver.py",
                 _T_CLASS % ("self.buf[b]", "  # worker: main")) == []
    # inline `# reprolint: ok T301` silences a commented legitimate read
    assert _lint(tmp_path, "src/repro/cohort/driver.py", """\
        class Loop:
            def __init__(self):
                self.trace = None  # owner: solve

            def result(self):  # worker: main
                return self.trace  # reprolint: ok T301
        """) == []


def test_t302_untagged_write_positive(tmp_path):
    out = _lint(tmp_path, "src/repro/cohort/driver.py",
                _T_CLASS % ("self.buf[b]", ""))
    assert _rules(out) == ["T302"]
    assert out[0].context == "Loop.fold"


def test_t302_untagged_read_negative(tmp_path):
    # untagged READS (introspection) stay legal; writes are the contract
    assert _lint(tmp_path, "src/repro/cohort/driver.py", """\
        class Loop:
            def __init__(self):
                self.buf = {}  # owner: pack

            def memory_bytes(self):
                return len(self.buf)
        """) == []


def test_t_multi_owner_tag(tmp_path):
    # `# owner: pack|solve` grants both workers access
    assert _lint(tmp_path, "src/repro/cohort/driver.py", """\
        class Loop:
            def __init__(self):
                self.q = []  # owner: pack|solve

            def push(self, b):  # worker: pack
                self.q.append(b)

            def pop(self):  # worker: solve
                return self.q.pop()
        """) == []


# -- U501 (import reachability) ---------------------------------------------

def _mini_repo(tmp_path, wire_config: bool):
    src = tmp_path / "src"
    files = {
        "repro/__init__.py": "",
        "repro/api/__init__.py":
            "from repro.core import run\n"
            + ("from repro.configs.used import CFG\n" if wire_config else ""),
        "repro/core/__init__.py": "def run():\n    return 1\n",
        "repro/configs/__init__.py": "",
        "repro/configs/used.py": "CFG = {}\n",
        "repro/configs/dead.py": "DEAD = {}\n",
    }
    for rel, text in files.items():
        p = src / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return tmp_path


def test_u501_unreachable_positive(tmp_path):
    root = _mini_repo(tmp_path, wire_config=False)
    names = sorted(f.snippet for f in graph.check_unreachable(root))
    # nothing imports configs at all -> the whole subtree is unreachable
    assert names == ["repro.configs", "repro.configs.dead",
                     "repro.configs.used"]


def test_u501_unreachable_negative(tmp_path):
    root = _mini_repo(tmp_path, wire_config=True)
    names = sorted(f.snippet for f in graph.check_unreachable(root))
    # wiring `used` reaches it AND the package init; only `dead` remains
    assert names == ["repro.configs.dead"]


# -- W401 (dynamic quickstart gate) -----------------------------------------

def test_w401_first_party_warning_positive(tmp_path):
    qs = tmp_path / "examples" / "quickstart.py"
    qs.parent.mkdir(parents=True)
    qs.write_text("import warnings\n"
                  "warnings.warn('old_call() is deprecated',"
                  " DeprecationWarning)\n")
    findings, notes = quickstart.check_quickstart(tmp_path, target=qs)
    assert _rules(findings) == ["W401"]
    assert "old_call() is deprecated" in findings[0].snippet
    assert notes == []


def test_w401_third_party_warning_negative(tmp_path):
    # a DeprecationWarning raised OUTSIDE the repo root is a note, not fatal
    dep = tmp_path / "elsewhere" / "dep.py"
    dep.parent.mkdir(parents=True)
    dep.write_text("import warnings\n"
                   "def f():\n"
                   "    warnings.warn('vendor churn', DeprecationWarning)\n")
    repo = tmp_path / "repo"
    qs = repo / "examples" / "quickstart.py"
    qs.parent.mkdir(parents=True)
    qs.write_text(f"import sys\nsys.path.insert(0, {str(dep.parent)!r})\n"
                  "import dep\ndep.f()\n")
    findings, notes = quickstart.check_quickstart(repo, target=qs)
    assert findings == []
    assert len(notes) == 1 and "vendor churn" in notes[0]


# -- baseline round-trip ----------------------------------------------------

def test_baseline_add_suppress_remove(tmp_path):
    f = tmp_path / "src/repro/core/foo.py"
    f.parent.mkdir(parents=True)
    f.write_text("import time\n\ndef f():\n    return time.perf_counter()\n")
    found = lint_file(tmp_path, f)
    assert _rules(found) == ["D101"]

    # add: accepted findings stop counting as new
    bl = tmp_path / "baseline.txt"
    baseline_mod.save(bl, found, header="test baseline")
    new, old, stale = baseline_mod.split(lint_file(tmp_path, f),
                                         baseline_mod.load(bl))
    assert (new, len(old), stale) == ([], 1, [])

    # the fingerprint is line-number-free: shifting the file does not churn
    f.write_text("import time\n\n\n\ndef f():\n    return "
                 "time.perf_counter()\n")
    new, old, stale = baseline_mod.split(lint_file(tmp_path, f),
                                         baseline_mod.load(bl))
    assert (new, len(old), stale) == ([], 1, [])

    # remove: fixing the violation turns the entry stale (reported, so the
    # baseline only ever shrinks by someone noticing)
    f.write_text("from repro.utils.timing import tick\n\ndef f():\n"
                 "    return tick()\n")
    new, old, stale = baseline_mod.split(lint_file(tmp_path, f),
                                         baseline_mod.load(bl))
    assert (new, old) == ([], []) and len(stale) == 1


# -- CLI + planted violations per family ------------------------------------

def test_cli_planted_violations_all_families(tmp_path, capsys):
    """One planted violation per static family (D/P/T) plus U501 must fail
    the CLI; baselining them must pass it."""
    _mini_repo(tmp_path, wire_config=False)
    bad = tmp_path / "src/repro/cohort/driver.py"
    bad.parent.mkdir(parents=True, exist_ok=True)
    bad.write_text(textwrap.dedent("""\
        import time
        import jax.numpy as jnp

        def gram(Xc):
            return jnp.matmul(Xc, Xc.T)

        class Loop:
            def __init__(self):
                self.buf = {}  # owner: pack

            def fold(self, b):  # worker: main
                self.buf[b] = time.time()
        """))
    bl = tmp_path / "baseline.txt"
    argv = ["--root", str(tmp_path), "--baseline", str(bl),
            str(tmp_path / "src" / "repro")]
    assert main(argv) == 1
    out = capsys.readouterr().out
    for rule in ("D101", "P201", "T301", "U501"):
        assert rule in out, f"planted {rule} violation not caught"

    assert main(argv + ["--write-baseline"]) == 0
    assert main(argv) == 0        # everything baselined -> clean exit


def test_cli_report_artifact(tmp_path):
    _mini_repo(tmp_path, wire_config=True)
    report = tmp_path / "findings.json"
    main(["--root", str(tmp_path), "--baseline", str(tmp_path / "b.txt"),
          "--report", str(report), str(tmp_path / "src" / "repro")])
    import json
    payload = json.loads(report.read_text())
    assert [f["rule"] for f in payload["new"]] == ["U501"]
    assert payload["baselined"] == [] and payload["stale_baseline"] == []


# -- the real tree (tier-1 gate) --------------------------------------------

def test_repo_tree_is_clean():
    """The shipped tree lints to zero non-baselined findings -- the same
    gate CI runs via `python -m tools.reprolint src/repro tools benchmarks`.
    """
    targets = [REPO_ROOT / "src" / "repro", REPO_ROOT / "tools",
               REPO_ROOT / "benchmarks"]
    findings = run_paths(REPO_ROOT, targets)
    known = baseline_mod.load(
        REPO_ROOT / "tools" / "reprolint" / "baseline.txt")
    new, old, stale = baseline_mod.split(findings, known)
    assert new == [], "\n".join(f.render() for f in new)
    assert stale == [], f"stale baseline entries: {stale}"
    # the baseline carries exactly the justified U501 modules, nothing else
    assert {f.rule for f in old} == {"U501"}
