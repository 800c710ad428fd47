"""The reprolint rule set: AST checks over single files.

Three static families (stdlib ``ast`` + ``tokenize`` only -- importing this
module must never import jax):

  * **D -- determinism**: the repo's results must be a pure function of
    (seed, config).  Wall-clock reads, stdlib ``random`` and unseeded numpy
    RNGs inside ``src/repro``/``benchmarks`` break that (DESIGN.md section
    "systems model": the only clock results may depend on is the simulated
    ``SystemsTrace``; real time is read solely through
    ``repro.utils.timing``).
  * **P -- parity contracts**: all three round engines must fold floats in
    one pinned order, which holds only while every engine goes through the
    fp_barrier'd chunk primitives in ``repro.core.subproblem``.  Raw
    re-derivations of those reductions (``X @ X.T``, manual row-dot sums)
    in engine/kernel code silently fork the contract, and host
    materialization inside scanned round functions breaks ``lax.scan``
    tracing.
  * **T -- thread ownership**: the overlapped cohort pipeline
    (``repro.cohort.driver``) is race-free by a commented ownership
    contract: ``# owner: pack|solve|main`` on attribute initialisation,
    ``# worker: <name>`` on methods.  T rules mechanically check tagged
    methods touch only attributes they own.

Scopes are glob patterns over repo-relative posix paths; ``fnmatch``'s
``*`` crosses ``/`` so ``src/repro/*`` means the whole subtree.

Suppression: a trailing ``# reprolint: ok RULEID`` (or bare
``# reprolint: ok``) on the flagged line silences it -- for the rare,
commented legitimate exception (e.g. a cross-owner read after the worker
pools have joined).
"""
from __future__ import annotations

import ast
import io
import re
import tokenize
from fnmatch import fnmatchcase
from pathlib import Path
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

from tools.reprolint.findings import Finding

SUPPRESS_RE = re.compile(r"#\s*reprolint:\s*ok\b\s*([A-Z]\d+)?")
OWNER_RE = re.compile(r"#\s*owner:\s*([\w|]+)")
WORKER_RE = re.compile(r"#\s*worker:\s*(\w+)")


# ---------------------------------------------------------------------------
# per-file context


def _comment_map(source: str) -> Dict[int, str]:
    """{line -> comment text} (ast drops comments; tokenize keeps them)."""
    out: Dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string
    except (tokenize.TokenError, IndentationError):
        pass
    return out


def _alias_map(tree: ast.AST) -> Dict[str, str]:
    """Local name -> dotted import path, from every import in the file.

    ``import jax.numpy as jnp`` -> {jnp: jax.numpy}; ``import time`` ->
    {time: time}; ``from numpy.random import default_rng`` ->
    {default_rng: numpy.random.default_rng}.  Relative imports are
    prefixed with ``.`` so they can never collide with the stdlib/numpy
    names the rules ban.
    """
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    aliases[a.asname] = a.name
                else:
                    aliases[a.name.split(".")[0]] = a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom):
            mod = ("." * node.level) + (node.module or "")
            for a in node.names:
                if a.name == "*":
                    continue
                aliases[a.asname or a.name] = f"{mod}.{a.name}"
    return aliases


def _qualname_map(tree: ast.AST) -> Dict[int, str]:
    """id(node) -> enclosing qualname ('' at module level)."""
    out: Dict[int, str] = {}

    def visit(node: ast.AST, stack: Tuple[str, ...]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                out[id(child)] = ".".join(stack) if stack else "<module>"
                visit(child, stack + (child.name,))
            else:
                out[id(child)] = ".".join(stack) if stack else "<module>"
                visit(child, stack)
    out[id(tree)] = "<module>"
    visit(tree, ())
    return out


class FileContext:
    """Parsed view of one source file, shared by every rule."""

    def __init__(self, root: Path, path: Path):
        self.path = path
        self.rel = path.resolve().relative_to(root.resolve()).as_posix()
        self.source = path.read_text()
        self.lines = self.source.splitlines()
        self.tree = ast.parse(self.source, filename=str(path))
        self.comments = _comment_map(self.source)
        self.aliases = _alias_map(self.tree)
        self.qualnames = _qualname_map(self.tree)

    def qualname(self, node: ast.AST) -> str:
        q = self.qualnames.get(id(node), "<module>")

        def enclosing(n: ast.AST) -> str:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                base = self.qualnames.get(id(n), "<module>")
                return n.name if base == "<module>" else f"{base}.{n.name}"
            return q
        return enclosing(node)

    def snippet(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Dotted name a call target resolves to, or None.

        ``tick()`` after ``from repro.utils.timing import tick`` resolves
        to ``repro.utils.timing.tick``; ``np.random.seed`` to
        ``numpy.random.seed`` -- modulo shadowing by local variables,
        which the repo's style makes a non-issue.
        """
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        base = self.aliases.get(node.id, node.id)
        return ".".join([base] + list(reversed(parts)))

    def suppressed(self, line: int, rule: str) -> bool:
        m = SUPPRESS_RE.search(self.comments.get(line, ""))
        return bool(m) and m.group(1) in (None, rule)

    def finding(self, rule: "Rule", node: ast.AST, message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        return Finding(rule=rule.id, path=self.rel, line=line,
                       message=message, context=self.qualname(node),
                       snippet=self.snippet(line), hint=rule.hint)


def _match(rel: str, patterns: Iterable[str]) -> bool:
    return any(fnmatchcase(rel, p) for p in patterns)


# ---------------------------------------------------------------------------
# rule base


class Rule:
    id: str = ""
    summary: str = ""
    hint: str = ""
    scope: Tuple[str, ...] = ()
    exempt: Tuple[str, ...] = ()

    def applies(self, rel: str) -> bool:
        return _match(rel, self.scope) and not _match(rel, self.exempt)

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        raise NotImplementedError

    def _calls(self, ctx: FileContext) -> Iterator[Tuple[ast.Call, str]]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                name = ctx.resolve(node.func)
                if name is not None:
                    yield node, name


# ---------------------------------------------------------------------------
# D family -- determinism


class D101WallClockRead(Rule):
    id = "D101"
    summary = ("direct wall-clock read; results must depend only on the "
               "simulated SystemsTrace clock")
    hint = ("measure through repro.utils.timing.tick()/timed() (the one "
            "sanctioned wall-clock module)")
    scope = ("src/repro/*", "benchmarks/*")
    exempt = ("src/repro/utils/timing.py",)

    BANNED = {
        "time.time", "time.time_ns", "time.perf_counter",
        "time.perf_counter_ns", "time.monotonic", "time.monotonic_ns",
        "time.process_time", "time.process_time_ns", "time.clock_gettime",
        "time.clock_gettime_ns",
    }

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node, name in self._calls(ctx):
            if name in self.BANNED:
                yield ctx.finding(self, node, f"wall-clock read `{name}`")


class D102StdlibRandom(Rule):
    id = "D102"
    summary = ("stdlib `random` is process-global, unseeded-by-default "
               "state; all repo randomness derives from (seed, id)")
    hint = ("use numpy.random.default_rng(seed)/SeedSequence or "
            "jax.random keys threaded from the config seed")
    scope = ("src/repro/*", "benchmarks/*")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "random" or a.name.startswith("random."):
                        yield ctx.finding(self, node,
                                          "import of stdlib `random`")
            elif isinstance(node, ast.ImportFrom):
                if node.level == 0 and node.module == "random":
                    yield ctx.finding(self, node,
                                      "import from stdlib `random`")
        for node, name in self._calls(ctx):
            if name.startswith("random.") and not ctx.suppressed(
                    node.lineno, self.id):
                yield ctx.finding(self, node,
                                  f"stdlib random call `{name}`")


class D103UnseededNumpyRng(Rule):
    id = "D103"
    summary = ("unseeded / legacy-global numpy RNG; every draw must be a "
               "pure function of (seed, id)")
    hint = ("numpy.random.default_rng(seed) (or SeedSequence(seed, id)); "
            "the legacy global numpy.random.* API is banned outright")
    scope = ("src/repro/*", "benchmarks/*")

    LEGACY = {
        "seed", "rand", "randn", "random", "randint", "uniform", "normal",
        "standard_normal", "choice", "shuffle", "permutation", "RandomState",
    }

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node, name in self._calls(ctx):
            if name == "numpy.random.default_rng" and not (node.args
                                                           or node.keywords):
                yield ctx.finding(self, node,
                                  "unseeded numpy.random.default_rng()")
            elif (name.startswith("numpy.random.")
                  and name.rsplit(".", 1)[1] in self.LEGACY):
                yield ctx.finding(
                    self, node, f"legacy global numpy RNG call `{name}`")


class D104BenchProvenanceTime(Rule):
    id = "D104"
    summary = ("calendar-time read in BENCH/report provenance code; rows "
               "must be reproducible byte-for-byte across reruns")
    hint = ("provenance identifies (config, code); if a timestamp is truly "
            "needed, pass it in explicitly at the entry point")
    scope = ("benchmarks/*", "src/repro/api/report.py",
             "src/repro/api/execute.py")

    BANNED = {
        "datetime.datetime.now", "datetime.datetime.utcnow",
        "datetime.datetime.today", "datetime.date.today",
        "time.strftime", "time.ctime", "time.asctime",
        "time.localtime", "time.gmtime",
    }

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node, name in self._calls(ctx):
            if name in self.BANNED:
                yield ctx.finding(self, node, f"calendar-time read `{name}`")


class D106TelemetryDiscipline(Rule):
    id = "D106"
    summary = ("telemetry discipline: repro.obs reads the wall clock only "
               "through repro.utils.timing, and span/registry internals "
               "never leave repro.obs (instrument through the Telemetry "
               "facade)")
    hint = ("inside src/repro/obs: import tick/timed from repro.utils.timing "
            "instead of stdlib `time`; everywhere else: obtain telemetry via "
            "obs.telemetry()/obs.NULL_TELEMETRY and emit through Telemetry's "
            "span/event/counter methods -- never import or construct "
            "Span/Tracer/MetricsRegistry directly (DESIGN.md section 11)")
    scope = ("src/repro/*", "benchmarks/*", "tools/*")
    exempt = ("tools/reprolint/*",)

    #: submodules whose contents are package-private to repro.obs
    INTERNAL_MODULES = ("repro.obs.tracer", "repro.obs.metrics",
                        "repro.obs.export")
    #: facade-level names that are still internals (only Telemetry views,
    #: telemetry(), NULL_TELEMETRY and the export helpers are public)
    INTERNAL_NAMES = {"Span", "Tracer", "NullTracer", "MetricsRegistry",
                      "NullRegistry"}

    @staticmethod
    def _inside_obs(rel: str) -> bool:
        return rel.startswith("src/repro/obs/")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if self._inside_obs(ctx.rel):
            yield from self._check_inside(ctx)
        else:
            yield from self._check_outside(ctx)

    def _check_inside(self, ctx: FileContext) -> Iterator[Finding]:
        # D101 already bans time.* CALLS repo-wide; banning the import here
        # keeps even an unused `import time` out of the telemetry package
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "time" or a.name.startswith("time."):
                        yield ctx.finding(
                            self, node, "stdlib `time` import inside "
                            "repro.obs; wall clock comes only from "
                            "repro.utils.timing")
            elif isinstance(node, ast.ImportFrom):
                if node.level == 0 and node.module == "time":
                    yield ctx.finding(
                        self, node, "import from stdlib `time` inside "
                        "repro.obs; wall clock comes only from "
                        "repro.utils.timing")

    def _check_outside(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name in self.INTERNAL_MODULES:
                        yield ctx.finding(
                            self, node,
                            f"import of obs internal module `{a.name}`")
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                mod = node.module or ""
                if mod in self.INTERNAL_MODULES:
                    yield ctx.finding(
                        self, node,
                        f"import from obs internal module `{mod}`")
                elif mod == "repro.obs":
                    for a in node.names:
                        if a.name in self.INTERNAL_NAMES:
                            yield ctx.finding(
                                self, node,
                                f"import of obs internal `{a.name}`; "
                                "instrument through the Telemetry facade")
        for node, name in self._calls(ctx):
            if not name.startswith("repro.obs."):
                continue
            tail = name[len("repro.obs."):]
            if (tail.split(".")[0] in ("tracer", "metrics", "export")
                    or tail in self.INTERNAL_NAMES):
                yield ctx.finding(
                    self, node,
                    f"ad-hoc obs internal call `{name}`; construct spans/"
                    "metrics only through a Telemetry view")


class D107ServeReadOnly(Rule):
    id = "D107"
    summary = ("serve-tier discipline: serving code only READS training "
               "state, and only through ServedSnapshot -- no RNG draws, no "
               "SystemsTrace writes, no mutable ClusterOmega import")
    hint = ("consume training state as a repro.serve.store.ServedSnapshot "
            "(published by the refresh loop); a prediction must be a pure "
            "function of (snapshot, ids, X) so serving can never perturb "
            "or race the training run (DESIGN.md section 12)")
    scope = ("src/repro/serve/*",)
    #: the LM decode demo engine samples tokens from its own seeded
    #: stream -- generation randomness, not training state
    exempt = ("src/repro/serve/engine.py",)

    #: any draw would make served answers depend on request order
    RNG_PREFIXES = ("jax.random.", "numpy.random.")
    #: the SystemsTrace mutation surface (simulated-clock writes belong to
    #: the solve worker, never to serving)
    TRACE_MUTATORS = {"begin_round", "commit", "charge", "set_rate_scale",
                      "replay"}
    #: the mutable training state; serve sees it only via ServedSnapshot
    BANNED_MODULE = "repro.cohort.omega"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == self.BANNED_MODULE:
                        yield ctx.finding(
                            self, node,
                            f"import of mutable training state module "
                            f"`{a.name}`; serve reads ServedSnapshot only")
            elif (isinstance(node, ast.ImportFrom) and node.level == 0
                    and node.module == self.BANNED_MODULE):
                yield ctx.finding(
                    self, node,
                    f"import from mutable training state module "
                    f"`{node.module}`; serve reads ServedSnapshot only")
        for node, name in self._calls(ctx):
            if name.startswith(self.RNG_PREFIXES):
                yield ctx.finding(
                    self, node, f"RNG draw `{name}` in serve code; served "
                    "answers must be pure in (snapshot, ids, X)")
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self.TRACE_MUTATORS
                    and not ctx.suppressed(node.lineno, self.id)):
                yield ctx.finding(
                    self, node,
                    f"trace-mutator call `.{node.func.attr}(...)` in serve "
                    "code; the SystemsTrace clock is training-owned")


class D105SilentFaultSwallow(Rule):
    id = "D105"
    summary = ("silent fault swallowing; failures must be retried, "
               "degraded, or raised -- never dropped")
    hint = ("route failures through repro.cohort.resilience (retry/"
            "degrade/BlockFailure) or narrow the except and handle it; a "
            "bare `except:` / `except Exception: pass` hides real faults "
            "from the resilience layer (DESIGN.md section 10)")
    scope = ("src/repro/*",)

    _BLANKET = {"Exception", "BaseException"}

    @staticmethod
    def _swallows(handler: ast.ExceptHandler) -> bool:
        """Body does nothing: only ``pass`` / ``...`` statements."""
        return all(
            isinstance(st, ast.Pass)
            or (isinstance(st, ast.Expr)
                and isinstance(st.value, ast.Constant)
                and st.value.value is Ellipsis)
            for st in handler.body)

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                yield ctx.finding(
                    self, node, "bare `except:` (catches everything, "
                    "including KeyboardInterrupt)")
            elif (isinstance(node.type, ast.Name)
                  and node.type.id in self._BLANKET
                  and self._swallows(node)):
                yield ctx.finding(
                    self, node,
                    f"`except {node.type.id}: pass` swallows faults "
                    "silently")


# ---------------------------------------------------------------------------
# P family -- parity contracts


def _same_expr(a: ast.AST, b: ast.AST) -> bool:
    return ast.dump(a) == ast.dump(b)


class P201RawSelfGram(Rule):
    id = "P201"
    summary = ("raw self-Gram product in engine/kernel code; all engines "
               "must share the fp_barrier'd chunk primitive")
    hint = ("import _chunk_gram / row_norms from repro.core.subproblem "
            "(the single pinned fold order all three engines share)")
    # core/subproblem.py itself DEFINES the primitive and is not in scope
    scope = ("src/repro/kernels/*", "src/repro/core/engine.py",
             "src/repro/federated/runtime.py", "src/repro/cohort/*")

    MATMULS = {"jax.numpy.matmul", "jax.numpy.dot", "numpy.matmul",
               "numpy.dot"}

    @staticmethod
    def _is_self_transpose(left: ast.AST, right: ast.AST) -> bool:
        return (isinstance(right, ast.Attribute) and right.attr == "T"
                and _same_expr(right.value, left))

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.MatMult)
                    and self._is_self_transpose(node.left, node.right)):
                yield ctx.finding(self, node, "raw `X @ X.T` self-Gram")
        for node, name in self._calls(ctx):
            if (name in self.MATMULS and len(node.args) >= 2
                    and self._is_self_transpose(node.args[0], node.args[1])):
                yield ctx.finding(self, node,
                                  f"raw self-Gram via `{name}(X, X.T)`")


class P202ManualRowReduction(Rule):
    id = "P202"
    summary = ("manual elementwise-product reduction in SDCA engine code; "
               "row-dot/colsum folds must go through the pinned primitives")
    hint = ("use _chunk_rowdots / _chunk_colsum / row_norms from "
            "repro.core.subproblem instead of sum(a * b)")
    scope = ("src/repro/kernels/sdca/*", "src/repro/core/engine.py",
             "src/repro/federated/runtime.py")

    SUMS = {"jax.numpy.sum", "numpy.sum"}

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node, name in self._calls(ctx):
            if (name in self.SUMS and node.args
                    and isinstance(node.args[0], ast.BinOp)
                    and isinstance(node.args[0].op, ast.Mult)):
                yield ctx.finding(
                    self, node, f"manual reduction `{name}(a * b)`")


class P203ScanHostMaterialization(Rule):
    id = "P203"
    summary = ("host materialization inside a scan_round_fn-registered "
               "function; traced values cannot cross to the host")
    hint = ("keep round bodies fully traced (jnp ops only); pull to host "
            "after the scan returns")
    scope = ("src/repro/*",)

    NP_MATERIALIZE = {"numpy.asarray", "numpy.array", "numpy.asanyarray"}

    @staticmethod
    def _registered_round_fns(tree: ast.AST) -> Set[str]:
        """Names returned by any ``scan_round_fn`` method in this module."""
        out: Set[str] = set()
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            for fn in cls.body:
                if (isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and fn.name == "scan_round_fn"):
                    for node in ast.walk(fn):
                        if (isinstance(node, ast.Return)
                                and isinstance(node.value, ast.Name)):
                            out.add(node.value.id)
        return out

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        registered = self._registered_round_fns(ctx.tree)
        if not registered:
            return
        for top in ctx.tree.body:
            if not (isinstance(top, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and top.name in registered):
                continue
            for node in ast.walk(top):
                if not isinstance(node, ast.Call):
                    continue
                if (isinstance(node.func, ast.Name)
                        and node.func.id == "float"):
                    yield ctx.finding(self, node,
                                      "`float(...)` on a traced value")
                elif (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item"):
                    yield ctx.finding(self, node,
                                      "`.item()` on a traced value")
                else:
                    name = ctx.resolve(node.func)
                    if name in self.NP_MATERIALIZE:
                        yield ctx.finding(
                            self, node,
                            f"`{name}` materializes a traced value")


# ---------------------------------------------------------------------------
# T family -- thread ownership (cohort pipeline)


class _OwnershipRule(Rule):
    scope = ("src/repro/cohort/*", "src/repro/serve/*")

    def _comment_in_span(self, ctx: FileContext, lo: int, hi: int,
                         pat: "re.Pattern") -> Optional[str]:
        for ln in range(lo, max(lo, hi) + 1):
            m = pat.search(ctx.comments.get(ln, ""))
            if m:
                return m.group(1)
        return None

    def _owners(self, ctx: FileContext,
                cls: ast.ClassDef) -> Dict[str, Set[str]]:
        """attr -> owner set, from ``# owner:`` comments on assignments."""
        owners: Dict[str, Set[str]] = {}
        for node in ast.walk(cls):
            if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                tag = self._comment_in_span(
                    ctx, node.lineno, getattr(node, "end_lineno", node.lineno),
                    OWNER_RE)
                if tag is None:
                    continue
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    for sub in ast.walk(t):
                        if (isinstance(sub, ast.Attribute)
                                and isinstance(sub.value, ast.Name)
                                and sub.value.id == "self"):
                            owners.setdefault(sub.attr, set()).update(
                                tag.split("|"))
        return owners

    def _worker_tag(self, ctx: FileContext,
                    fn: ast.FunctionDef) -> Optional[str]:
        hi = fn.body[0].lineno - 1 if fn.body else fn.lineno
        return self._comment_in_span(ctx, fn.lineno, max(fn.lineno, hi),
                                     WORKER_RE)

    def _classes(self, ctx: FileContext) -> Iterator[
            Tuple[ast.ClassDef, Dict[str, Set[str]]]]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef):
                owners = self._owners(ctx, node)
                if owners:
                    yield node, owners

    @staticmethod
    def _self_attrs(fn: ast.AST) -> Iterator[ast.Attribute]:
        for node in ast.walk(fn):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"):
                yield node


class T301WrongWorkerAccess(_OwnershipRule):
    id = "T301"
    summary = ("worker-tagged method touches an attribute owned by a "
               "different worker (a data race in the overlapped pipeline)")
    hint = ("access the attribute from its owning worker, hand the value "
            "across via the block queue, or update the `# owner:` contract")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for cls, owners in self._classes(ctx):
            for fn in cls.body:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                if fn.name == "__init__":
                    continue
                worker = self._worker_tag(ctx, fn)
                if worker is None:
                    continue
                for attr in self._self_attrs(fn):
                    own = owners.get(attr.attr)
                    if own is not None and worker not in own:
                        yield ctx.finding(
                            self, attr,
                            f"`self.{attr.attr}` is owned by "
                            f"{'|'.join(sorted(own))} but accessed from a "
                            f"`# worker: {worker}` method")


class T302UntaggedOwnedWrite(_OwnershipRule):
    id = "T302"
    summary = ("untagged method writes an owned attribute; writes must "
               "come from a `# worker:`-tagged method so the ownership "
               "contract stays checkable")
    hint = ("tag the method with `# worker: <owner>` (reads from untagged "
            "introspection helpers are fine; writes are not)")

    #: method calls that mutate their receiver -- `self.buf.append(x)` is a
    #: write to `buf` even though the Attribute node's ctx is Load
    MUTATORS = frozenset({
        "append", "extend", "insert", "pop", "popitem", "clear", "update",
        "add", "remove", "discard", "setdefault", "move_to_end", "sort",
        "reverse", "fill", "put", "put_nowait",
    })

    @classmethod
    def _written_attrs(cls, fn: ast.AST) -> Iterator[ast.Attribute]:
        def is_self_attr(n: ast.AST) -> bool:
            return (isinstance(n, ast.Attribute)
                    and isinstance(n.value, ast.Name)
                    and n.value.id == "self")

        for node in ast.walk(fn):
            if is_self_attr(node) and isinstance(node.ctx,
                                                 (ast.Store, ast.Del)):
                yield node                       # self.x = ... / del self.x
            elif (isinstance(node, ast.Subscript)
                    and isinstance(node.ctx, (ast.Store, ast.Del))
                    and is_self_attr(node.value)):
                yield node.value                 # self.x[i] = ...
            elif (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in cls.MUTATORS
                    and is_self_attr(node.func.value)):
                yield node.func.value            # self.x.append(...)

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for cls_node, owners in self._classes(ctx):
            for fn in cls_node.body:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                if fn.name == "__init__":
                    continue
                if self._worker_tag(ctx, fn) is not None:
                    continue
                for attr in self._written_attrs(fn):
                    if attr.attr in owners:
                        yield ctx.finding(
                            self, attr,
                            f"untagged method writes owned attribute "
                            f"`self.{attr.attr}`")


ALL_RULES: Tuple[Rule, ...] = (
    D101WallClockRead(), D102StdlibRandom(), D103UnseededNumpyRng(),
    D104BenchProvenanceTime(), D105SilentFaultSwallow(),
    D106TelemetryDiscipline(), D107ServeReadOnly(),
    P201RawSelfGram(), P202ManualRowReduction(),
    P203ScanHostMaterialization(),
    T301WrongWorkerAccess(), T302UntaggedOwnedWrite(),
)


def lint_file(root: Path, path: Path,
              rules: Iterable[Rule] = ALL_RULES) -> List[Finding]:
    """All non-suppressed findings for one file."""
    try:
        rel = path.resolve().relative_to(root.resolve()).as_posix()
    except ValueError:
        return []
    active = [r for r in rules if r.applies(rel)]
    if not active:
        return []
    try:
        ctx = FileContext(root, path)
    except (SyntaxError, UnicodeDecodeError):
        return [Finding(rule="E000", path=rel, line=1,
                        message="file does not parse", context="<module>",
                        snippet="", hint="fix the syntax error")]
    out: List[Finding] = []
    for rule in active:
        for f in rule.check(ctx):
            if not ctx.suppressed(f.line, f.rule):
                out.append(f)
    return out
