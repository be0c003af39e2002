"""mxlint rule families (ISSUE 5): retrace hazards, host-sync leaks,
lock discipline, knob registry.

Every rule is deliberately framework-aware and best-effort: it flags
the patterns that have actually bitten this codebase, with the
suppression comment as the escape hatch — NOT a general-purpose
soundness analysis.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set

from .core import (FileCtx, Finding, Rule, dotted_name,
                   load_knobs_module, _GUARDED_RE)

# ----------------------------------------------------------------------
# jit-body discovery (shared by the retrace rules)
# ----------------------------------------------------------------------
_JIT_NAMES = {"jit", "pjit"}


def _is_jit_callable(node: ast.AST) -> bool:
    """``jit`` / ``jax.jit`` / ``jax.experimental.pjit.pjit`` refs."""
    d = dotted_name(node)
    if d is None:
        return False
    last = d.rsplit(".", 1)[-1]
    return last in _JIT_NAMES


def _is_jit_call(node: ast.AST) -> bool:
    """``jax.jit(...)`` — including ``partial(jax.jit, ...)``."""
    if not isinstance(node, ast.Call):
        return False
    if _is_jit_callable(node.func):
        return True
    d = dotted_name(node.func)
    if d is not None and d.rsplit(".", 1)[-1] == "partial":
        return any(_is_jit_callable(a) for a in node.args)
    return False


def find_jit_bodies(tree: ast.AST) -> List[ast.AST]:
    """Function defs (or lambdas) that become jit entries:

    * decorated with ``@jit`` / ``@jax.jit`` /
      ``@partial(jax.jit, ...)``;
    * a ``def f`` whose NAME is later passed to a ``jax.jit(...)``
      call anywhere in the module;
    * a lambda appearing directly inside a ``jax.jit(...)`` call.
    """
    jitted_names: Set[str] = set()
    bodies: List[ast.AST] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit_call(node):
            for a in node.args:
                if isinstance(a, ast.Name):
                    jitted_names.add(a.id)
                elif isinstance(a, ast.Lambda):
                    bodies.append(a)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name in jitted_names:
                bodies.append(node)
            elif any(_is_jit_call(d) or _is_jit_callable(d)
                     for d in node.decorator_list):
                bodies.append(node)
    return bodies


def _param_names(fn: ast.AST) -> Set[str]:
    args = fn.args
    names = [a.arg for a in
             args.posonlyargs + args.args + args.kwonlyargs]
    if args.vararg:
        names.append(args.vararg.arg)
    if args.kwarg:
        names.append(args.kwarg.arg)
    return {n for n in names if n != "self"}


# ----------------------------------------------------------------------
# retrace rules
# ----------------------------------------------------------------------
_IMPURE_EXACT = {
    "time.time", "time.perf_counter", "time.monotonic", "time.sleep",
    "time.process_time", "time.time_ns", "time.perf_counter_ns",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "os.getenv", "os.urandom", "uuid.uuid4", "input",
}
_IMPURE_PREFIX = ("random.", "np.random.", "numpy.random.",
                  "os.environ.", "secrets.")
# jax.random / self._rng etc. must NOT match: prefixes anchor at the
# full dotted chain, so "jax.random.split" is safe.


class RetraceImpureCall(Rule):
    """Host-impure calls in a jit body run ONCE at trace time and are
    baked into the compiled program — time stands still, randomness
    freezes, env reads go stale.

    Inside the deterministic scope (``mxtpu/quant/`` — INT8
    calibration promises byte-identical thresholds across runs, and
    quant_policy.json commits them) the scan widens from jit bodies to
    EVERY function body: an RNG or clock call anywhere in the
    calibration tier silently breaks the committed evidence.  ``print``
    stays allowed there — it is non-deterministic only in a trace."""

    name = "retrace-impure-call"
    _DETERMINISTIC_SCOPE = ("mxtpu/quant/",)

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        deterministic = ctx.rel.startswith(self._DETERMINISTIC_SCOPE)
        if deterministic:
            bodies = [n for n in ast.walk(ctx.tree)
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef,
                                        ast.Lambda))]
        else:
            bodies = find_jit_bodies(ctx.tree)
        for body in bodies:
            for node in ast.walk(body):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted_name(node.func)
                if d is None:
                    continue
                if d in _IMPURE_EXACT or \
                        any(d.startswith(p) for p in _IMPURE_PREFIX) \
                        or (d == "print" and not deterministic):
                    out.append(Finding(
                        self.name, ctx.rel, node.lineno,
                        f"impure call `{d}` in the deterministic "
                        f"calibration scope breaks byte-reproducible "
                        f"thresholds (quant_policy.json evidence)"
                        if deterministic else
                        f"impure call `{d}` inside a jit body executes "
                        f"once at trace time and is constant-folded "
                        f"into the compiled program"))
        return out


_SHAPE_ATTRS = {"shape", "ndim", "dtype", "size"}


class RetraceTracedBranch(Rule):
    """``if``/``while`` on a traced parameter's VALUE forces a
    concretization error or per-value retrace.  Branching on shape,
    dtype, or None-ness is static under tracing and allowed."""

    name = "retrace-traced-branch"

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        for body in find_jit_bodies(ctx.tree):
            params = _param_names(body)
            if not params or isinstance(body, ast.Lambda):
                continue
            for node in ast.walk(body):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                bad = self._value_use(node.test, params)
                if bad:
                    out.append(Finding(
                        self.name, ctx.rel, node.lineno,
                        f"branching on traced parameter `{bad}`'s "
                        f"value inside a jit body (use jnp.where/"
                        f"lax.cond, or make it a static arg)"))
        return out

    def _value_use(self, test: ast.AST, params: Set[str]
                   ) -> Optional[str]:
        """First param whose VALUE (not shape/dtype/None-ness) feeds
        the condition."""
        # `x is None` / `x is not None` guards are static
        if isinstance(test, ast.Compare) and \
                all(isinstance(op, (ast.Is, ast.IsNot))
                    for op in test.ops):
            return None
        return self._scan(test, params)

    def _scan(self, node: ast.AST, params: Set[str]) -> Optional[str]:
        if isinstance(node, ast.Attribute):
            if node.attr in _SHAPE_ATTRS:
                return None  # static metadata access
            return self._scan(node.value, params)
        if isinstance(node, ast.Name):
            return node.id if node.id in params else None
        if isinstance(node, ast.Call):
            d = dotted_name(node.func)
            if d in ("len", "isinstance", "hasattr", "getattr",
                     "callable", "type"):
                return None  # static under tracing
            for a in list(node.args) + [kw.value
                                        for kw in node.keywords]:
                hit = self._scan(a, params)
                if hit:
                    return hit
            return None
        if isinstance(node, ast.Compare):
            for sub in [node.left] + list(node.comparators):
                hit = self._scan(sub, params)
                if hit:
                    return hit
            return None
        for child in ast.iter_child_nodes(node):
            hit = self._scan(child, params)
            if hit:
                return hit
        return None


class RetraceInlineJit(Rule):
    """``jax.jit(f)(x)`` — a fresh jit wrapper invoked immediately.
    When ``f`` is a fresh closure/lambda the cache never hits and
    every call recompiles (the exact churn mxtpu.guards catches at
    runtime)."""

    name = "retrace-inline-jit"

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Call) and \
                    _is_jit_call(node.func):
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    "inline `jax.jit(...)(...)` immediate invocation "
                    "— bind the jitted callable once (or AOT "
                    "lower/compile) so the cache can hit"))
        return out


_CONCRETIZE_METHODS = {"item", "tolist", "asnumpy"}
_CONCRETIZE_FUNCS = {"np.asarray", "np.array", "numpy.asarray",
                     "numpy.array", "float", "bool"}


class RetraceConcretize(Rule):
    """Concretizing a traced value (``float()``, ``np.asarray``,
    ``.item()``) inside a jit body either raises a
    ConcretizationTypeError or silently constant-folds."""

    name = "retrace-concretize"

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        for body in find_jit_bodies(ctx.tree):
            params = _param_names(body)
            for node in ast.walk(body):
                if not isinstance(node, ast.Call):
                    continue
                if isinstance(node.func, ast.Attribute) and \
                        node.func.attr in _CONCRETIZE_METHODS and \
                        not node.args:
                    out.append(Finding(
                        self.name, ctx.rel, node.lineno,
                        f"`.{node.func.attr}()` inside a jit body "
                        f"concretizes a traced value"))
                    continue
                d = dotted_name(node.func)
                if d in _CONCRETIZE_FUNCS and node.args and \
                        self._touches_param(node.args[0], params):
                    out.append(Finding(
                        self.name, ctx.rel, node.lineno,
                        f"`{d}(...)` on a traced parameter inside a "
                        f"jit body concretizes it (use jnp/lax ops)"))
        return out

    @staticmethod
    def _touches_param(node: ast.AST, params: Set[str]) -> bool:
        return any(isinstance(n, ast.Name) and n.id in params
                   for n in ast.walk(node))


# ----------------------------------------------------------------------
# host-sync leaks (files marked `# mxlint: hot-path`)
# ----------------------------------------------------------------------
_SYNC_METHODS = {"item", "tolist", "asnumpy", "block_until_ready"}
_SYNC_FUNCS = {"np.asarray", "np.array", "numpy.asarray",
               "numpy.array", "jax.device_get", "float", "bool"}


class HostSync(Rule):
    """In hot-path files, device→host syncs stall the dispatch
    pipeline (the asnumpy() trap).  Deliberate materialization points
    carry ``# mxlint: sync-point``."""

    name = "host-sync"

    def check(self, ctx: FileCtx) -> List[Finding]:
        if not ctx.hot_path:
            return []
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if node.lineno in ctx.sync_points:
                continue
            label = None
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _SYNC_METHODS and not node.args:
                label = f".{node.func.attr}()"
            else:
                d = dotted_name(node.func)
                if d in _SYNC_FUNCS:
                    if d in ("float", "bool") and (
                            not node.args or isinstance(
                                node.args[0], ast.Constant)):
                        continue
                    label = f"{d}(...)"
            if label:
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    f"{label} in a hot-path file forces a device→host "
                    f"sync; move it off the hot path or annotate the "
                    f"line `# mxlint: sync-point`"))
        return out


# ----------------------------------------------------------------------
# lock discipline (`# guarded-by: <lock>` annotations)
# ----------------------------------------------------------------------
class LockDiscipline(Rule):
    """``self.<attr>`` annotated ``# guarded-by: <lock>`` may only be
    touched inside ``with self.<lock>:``.  ``__init__`` (no concurrent
    access before construction completes) and methods named
    ``*_locked`` (documented called-with-lock-held convention) are
    exempt."""

    name = "lock-discipline"

    _ASSIGN_RE = re.compile(r"self\.(\w+)\s*(?::[^=]*)?=[^=]")

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        for cls in ast.walk(ctx.tree):
            if isinstance(cls, ast.ClassDef):
                out.extend(self._check_class(ctx, cls))
        return out

    def _annotations(self, ctx: FileCtx,
                     cls: ast.ClassDef) -> Dict[str, str]:
        """attr -> lock name, from guarded-by comments inside the
        class body's line range."""
        end = cls.end_lineno or len(ctx.lines)
        guarded: Dict[str, str] = {}
        for i in range(cls.lineno, end + 1):
            line = ctx.lines[i - 1] if i <= len(ctx.lines) else ""
            m = _GUARDED_RE.search(line)
            if not m:
                continue
            lock = m.group(1)
            # the guarded attribute: assignment on this line, else on
            # the next (annotation above a multi-line statement)
            am = self._ASSIGN_RE.search(line)
            if am is None and i < len(ctx.lines):
                am = self._ASSIGN_RE.search(ctx.lines[i])
            if am:
                guarded[am.group(1)] = lock
        return guarded

    def _check_class(self, ctx: FileCtx,
                     cls: ast.ClassDef) -> List[Finding]:
        guarded = self._annotations(ctx, cls)
        if not guarded:
            return []
        out: List[Finding] = []
        for meth in cls.body:
            if not isinstance(meth, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if meth.name == "__init__" or meth.name.endswith("_locked"):
                continue
            self._walk(ctx, meth, guarded, frozenset(), out)
        return out

    def _held_after(self, node: ast.With,
                    held: frozenset) -> frozenset:
        extra = set()
        for item in node.items:
            expr = item.context_expr
            if isinstance(expr, ast.Attribute) and \
                    isinstance(expr.value, ast.Name) and \
                    expr.value.id == "self":
                extra.add(expr.attr)
        return held | extra

    def _walk(self, ctx: FileCtx, node: ast.AST, guarded: Dict[str, str],
              held: frozenset, out: List[Finding]) -> None:
        if isinstance(node, (ast.With, ast.AsyncWith)):
            held = self._held_after(node, held)
        elif isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and \
                node.value.id == "self" and node.attr in guarded:
            lock = guarded[node.attr]
            if lock not in held:
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    f"`self.{node.attr}` is `# guarded-by: {lock}` but "
                    f"accessed outside `with self.{lock}:`"))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)) and held:
            # a nested def/lambda does not inherit the enclosing
            # lock scope — it may run later, unlocked
            held = frozenset()
        for child in ast.iter_child_nodes(node):
            self._walk(ctx, child, guarded, held, out)


# ----------------------------------------------------------------------
# knob registry rules
# ----------------------------------------------------------------------
def _knob_registry_names() -> Set[str]:
    return set(load_knobs_module().registered())


class _KnobRuleBase(Rule):
    _registry: Optional[Set[str]] = None

    @property
    def registry(self) -> Set[str]:
        if _KnobRuleBase._registry is None:
            _KnobRuleBase._registry = _knob_registry_names()
        return _KnobRuleBase._registry


class KnobRawEnv(_KnobRuleBase):
    """``os.environ`` reads of ``MXTPU_*``/``MXNET_*`` names must go
    through ``mxtpu.knobs.get`` — the registry is the single source of
    typing, defaults, and the README table.  Writes (launch scripts,
    ablation probes) are allowed."""

    name = "knob-raw-env"
    _EXEMPT = ("mxtpu/knobs.py", "mxtpu/base.py")

    def check(self, ctx: FileCtx) -> List[Finding]:
        if ctx.rel in self._EXEMPT:
            return []
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            knob = self._env_read(node)
            if knob:
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    f"raw environment read of `{knob}` — use "
                    f"`mxtpu.knobs.get(\"{knob}\")`"))
        return out

    @staticmethod
    def _literal_knob(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Constant) and \
                isinstance(node.value, str) and \
                node.value.startswith(("MXTPU_", "MXNET_")):
            return node.value
        return None

    def _env_read(self, node: ast.AST) -> Optional[str]:
        # os.environ.get("X") / os.environ.setdefault("X", ...) /
        # os.getenv("X")
        if isinstance(node, ast.Call):
            d = dotted_name(node.func)
            if d in ("os.environ.get", "os.environ.setdefault",
                     "os.getenv") and node.args:
                return self._literal_knob(node.args[0])
            return None
        # os.environ["X"] reads (Load context only — assignment to
        # os.environ["X"] is a write)
        if isinstance(node, ast.Subscript) and \
                isinstance(node.ctx, ast.Load) and \
                dotted_name(node.value) == "os.environ":
            return self._literal_knob(node.slice)
        return None


class KnobUnregistered(_KnobRuleBase):
    """``knobs.get("NAME")`` must name a registered knob (knobs.get
    raises at runtime; the lint catches it before that)."""

    name = "knob-unregistered"

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute) and
                    node.func.attr == "get" and
                    isinstance(node.func.value, ast.Name) and
                    node.func.value.id == "knobs" and node.args):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and \
                    isinstance(arg.value, str) and \
                    arg.value not in self.registry:
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    f"knobs.get({arg.value!r}): not registered in "
                    f"mxtpu/knobs.py"))
        return out


# ----------------------------------------------------------------------
# compiled-artifact discipline (tests/ only)
# ----------------------------------------------------------------------
class HloRawAssert(Rule):
    """Tests must not inspect compiled artifacts raw: ``.hlo_text(``
    / ``.as_text(`` grepping and manual ``.lower(x)`` chains in
    ``tests/`` fragment the HLO-parsing story ISSUE 6 consolidated
    into ``mxtpu.analysis`` (``program_summary`` /
    ``compiled_summary`` / ``compiled_evidence``).  Argument-less
    ``.lower()`` is string casing and stays exempt.  Suppress a
    deliberate exception with ``# mxlint: disable=hlo-raw-assert``."""

    name = "hlo-raw-assert"
    _TEXT_ATTRS = ("hlo_text", "as_text")

    def applies(self, ctx: FileCtx) -> bool:
        return ctx.rel.startswith("tests/")

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute)):
                continue
            attr = node.func.attr
            if attr in self._TEXT_ATTRS:
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    f"raw `.{attr}()` in a test — assert on "
                    f"`program_summary()` / "
                    f"`mxtpu.analysis.compiled_summary` instead"))
            elif attr == "lower" and (node.args or node.keywords):
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    "manual `.lower(...)` in a test — use "
                    "`mxtpu.analysis.compiled_artifact` (or the "
                    "TrainStep/ModelRunner summary APIs) so contract "
                    "checks stay on one parser"))
        return out


class MemHygiene(Rule):
    """Tests must not grep memory facts raw: ``.memory_analysis()``
    and ``.opt_state_bytes()`` calls in ``tests/`` fragment the
    byte-accounting story ISSUE 20 consolidated into
    ``mxtpu.analysis.memflow`` — assert on the sanctioned
    ``memory_summary()`` view (TrainStep / ModelRunner /
    GenerateRunner) or ``last_memory_analysis()`` instead, so the
    ``hbm_peak`` convention and the decomposition stay on one
    analyzer.  Suppress a deliberate exception with
    ``# mxlint: disable=mem-hygiene``."""

    name = "mem-hygiene"
    _MEM_ATTRS = ("memory_analysis", "opt_state_bytes")

    def applies(self, ctx: FileCtx) -> bool:
        return ctx.rel.startswith("tests/")

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute)):
                continue
            attr = node.func.attr
            if attr in self._MEM_ATTRS:
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    f"raw `.{attr}()` in a test — assert on "
                    f"`memory_summary()` (or "
                    f"`last_memory_analysis()`) so byte accounting "
                    f"stays on the one memflow analyzer"))
        return out


class ObsRegistry(Rule):
    """Metrics go through the ``mxtpu.obs`` registry, correctly named
    (ISSUE 8).  Three checks:

    * literal instrument names in ``obs.counter/gauge/histogram``
      calls must follow the convention — ``mxtpu_`` snake_case prefix,
      counters end ``_total``, histograms end ``_seconds`` / ``_us``
      / ``_bytes`` (the registry raises at runtime too; the lint
      catches it before the code path runs);
    * no ad-hoc module-level counters (``_N_CALLS = 0`` style) in the
      serving/parallel hot paths — those belong on the registry or on
      a locked instance attribute;
    * no ``profiler.Counter`` instances in serving/parallel — the
      chrome-trace counter is for trace dumps, not for metrics the
      registry should own.

    Suppress a deliberate exception with
    ``# mxlint: disable=obs-registry``."""

    name = "obs-registry"
    _FACTORIES = {"counter", "gauge", "histogram"}
    _NAME_RE = re.compile(r"^mxtpu_[a-z][a-z0-9_]*$")
    _HIST_SUFFIXES = ("_seconds", "_us", "_bytes")
    _COUNTERISH = re.compile(
        r"(?:^|_)(?:n|num|count|counts|counter|total|totals|hits|"
        r"misses|calls)(?:_|$)", re.IGNORECASE)
    _HOT_DIRS = ("mxtpu/serving/", "mxtpu/parallel/")

    def _name_findings(self, ctx: FileCtx, node: ast.Call,
                       kind: str) -> List[Finding]:
        if not node.args or not isinstance(node.args[0], ast.Constant) \
                or not isinstance(node.args[0].value, str):
            return []
        name = node.args[0].value
        bad: Optional[str] = None
        if not self._NAME_RE.match(name):
            bad = ("instrument name must match "
                   "`mxtpu_[a-z][a-z0-9_]*`")
        elif kind == "counter" and not name.endswith("_total"):
            bad = "counter names end `_total`"
        elif kind == "histogram" and \
                not name.endswith(self._HIST_SUFFIXES):
            bad = ("histogram names end `_seconds` / `_us` / "
                   "`_bytes` (name the unit)")
        if bad is None:
            return []
        return [Finding(self.name, ctx.rel, node.lineno,
                        f"obs.{kind}({name!r}): {bad}")]

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        in_hot = ctx.rel.startswith(self._HOT_DIRS)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if d is None:
                continue
            head, _, last = d.rpartition(".")
            if last in self._FACTORIES and head.endswith("obs"):
                out.extend(self._name_findings(ctx, node, last))
            elif in_hot and d.endswith("profiler.Counter"):
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    "profiler.Counter in a serving/parallel hot path "
                    "— publish through the mxtpu.obs registry (the "
                    "chrome-trace counter is a trace artifact, not "
                    "the metrics surface)"))
        if in_hot:
            for stmt in ctx.tree.body:
                if not isinstance(stmt, ast.Assign):
                    continue
                if not (isinstance(stmt.value, ast.Constant) and
                        isinstance(stmt.value.value, int) and
                        not isinstance(stmt.value.value, bool)):
                    continue
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Name) and \
                            self._COUNTERISH.search(tgt.id):
                        out.append(Finding(
                            self.name, ctx.rel, stmt.lineno,
                            f"module-level counter `{tgt.id}` in a "
                            f"serving/parallel hot path — use an "
                            f"obs registry counter (process-wide, "
                            f"locked, scrapeable) instead"))
        return out


class ThreadHygiene(Rule):
    """Threading discipline for the serving/obs stack (mxrace
    satellite): no bare ``time.sleep()`` polling loops — waiters must
    be interruptible (``Event.wait(timeout)`` / ``Condition.wait``) or
    clock-injected so shutdown and sync-mode tests don't block on wall
    time — and every ``threading.Thread`` is ``daemon=True`` (shutdown
    is join-with-timeout + daemon fallback; a non-daemon worker the
    close path misses wedges interpreter exit, which is exactly what
    the conftest thread-leak gate fails tests for)."""

    name = "thread-hygiene"
    _SCOPE = ("mxtpu/serving/", "mxtpu/obs/")

    def applies(self, ctx: FileCtx) -> bool:
        return ctx.rel.startswith(self._SCOPE)

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        sleeps: Dict[tuple, ast.Call] = {}
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.While, ast.For)):
                continue
            for sub in ast.walk(loop):
                if isinstance(sub, ast.Call) and \
                        dotted_name(sub.func) == "time.sleep":
                    sleeps[(sub.lineno, sub.col_offset)] = sub
        for key in sorted(sleeps):
            out.append(Finding(
                self.name, ctx.rel, sleeps[key].lineno,
                "bare time.sleep() in a loop — wait on an "
                "Event/Condition with a timeout (or the injected "
                "clock) so shutdown and sync-mode tests can "
                "interrupt it"))
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if d is None or \
                    not (d == "Thread" or d.endswith("threading.Thread")):
                continue
            daemon = next((kw.value for kw in node.keywords
                           if kw.arg == "daemon"), None)
            if not (isinstance(daemon, ast.Constant)
                    and daemon.value is True):
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    "threading.Thread without daemon=True in "
                    "serving/obs — a worker the close path misses "
                    "must not wedge interpreter exit; set "
                    "daemon=True and join with a timeout"))
        return out


class DtypeHygiene(Rule):
    """Precision discipline for library code (mxprec satellite): no
    ad-hoc f64.  ``np.float64``/``jnp.float64`` literals,
    ``.astype("float64")``, and ``jax.config.update("jax_enable_x64",
    ...)`` in ``mxtpu/`` silently double memory/compute and poison the
    bf16/f32 dtype story the precision ledgers pin — f64 is a
    per-callsite decision that needs the pragma as a visible waiver.
    Tests are exempt (seeding f64 to exercise the f64-creep rule is
    their job)."""

    name = "dtype-hygiene"
    _F64_ATTRS = {"np.float64", "numpy.float64", "jnp.float64",
                  "jax.numpy.float64"}

    def applies(self, ctx: FileCtx) -> bool:
        return ctx.rel.startswith("mxtpu/")

    def _is_f64_arg(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Constant) and node.value == "float64":
            return True
        return dotted_name(node) in self._F64_ATTRS

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        claimed: Set[int] = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if d is not None and d.endswith("config.update") and \
                    node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    node.args[0].value == "jax_enable_x64":
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    "jax_enable_x64 toggled in library code — x64 is "
                    "process-global and breaks the bf16/f32 policy "
                    "contracts/prec/ pins; scope it to the caller "
                    "(jax.enable_x64) or waive with a "
                    "pragma"))
                continue
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "astype":
                for a in node.args:
                    if self._is_f64_arg(a):
                        claimed.add(id(a))
                        out.append(Finding(
                            self.name, ctx.rel, node.lineno,
                            ".astype(float64) in library code — f64 "
                            "doubles memory/compute and trips "
                            "mxprec's f64-creep rule; accumulate in "
                            "f32 (or waive with a pragma where f64 "
                            "is the point)"))
        for node in ast.walk(ctx.tree):
            if id(node) in claimed or \
                    dotted_name(node) not in self._F64_ATTRS:
                continue
            out.append(Finding(
                self.name, ctx.rel, node.lineno,
                "float64 literal in library code — silent f32->f64 "
                "promotion (mxprec's f64-creep rule names the "
                "compiled sites); use f32 or waive with a pragma"))
        return sorted(out, key=lambda f: f.line)


class NoAdhocBf16(Rule):
    """The AMP pass is the ONE cast authority (r15): bf16 edges are
    decided by ``contracts/amp_policy.json`` at the op-dispatch choke
    point, so the six ``*_amp`` precision ledgers describe every
    program.  A hand-rolled bf16 cast in a model/layer hot path
    (``mxtpu/models/``, ``mxtpu/gluon/``) bypasses the policy veto and
    the f32-accumulation rule — it can reintroduce exactly the bf16
    accumulating reductions mxprec exists to catch, invisibly to the
    ledgers.  Waive a deliberate site (an I/O boundary, a test
    fixture block) with ``# mxlint: disable=no-adhoc-bf16`` and say
    why."""

    name = "no-adhoc-bf16"
    _BF16_ATTRS = {"np.bfloat16", "numpy.bfloat16", "jnp.bfloat16",
                   "jax.numpy.bfloat16", "ml_dtypes.bfloat16"}
    _BF16_STRINGS = {"bfloat16", "bf16"}
    _CASTERS = {"astype", "cast", "cast_all"}

    def applies(self, ctx: FileCtx) -> bool:
        return ctx.rel.startswith(("mxtpu/models/", "mxtpu/gluon/"))

    def _is_bf16_arg(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Constant) and \
                node.value in self._BF16_STRINGS:
            return True
        return dotted_name(node) in self._BF16_ATTRS

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        claimed: Set[int] = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = node.func.attr \
                if isinstance(node.func, ast.Attribute) else (
                    node.func.id if isinstance(node.func, ast.Name)
                    else None)
            args = list(node.args) + [kw.value for kw in node.keywords
                                      if kw.arg == "dtype"]
            if callee not in self._CASTERS:
                # a dtype="bfloat16" kwarg on any call (array ctor,
                # layer ctor) plants ad-hoc bf16 state just the same
                args = [kw.value for kw in node.keywords
                        if kw.arg == "dtype"]
            for a in args:
                if self._is_bf16_arg(a):
                    claimed.add(id(a))
                    out.append(Finding(
                        self.name, ctx.rel, node.lineno,
                        "ad-hoc bf16 cast in a model/layer hot path — "
                        "bf16 edges belong to the policy-driven AMP "
                        "pass (amp=True consumes contracts/"
                        "amp_policy.json with f32 accumulation); a "
                        "hand cast bypasses the policy veto and the "
                        "*_amp ledgers, or waive with a pragma "
                        "stating why this site is exempt"))
        for node in ast.walk(ctx.tree):
            if id(node) in claimed or \
                    dotted_name(node) not in self._BF16_ATTRS:
                continue
            out.append(Finding(
                self.name, ctx.rel, node.lineno,
                "bfloat16 literal in a model/layer hot path — route "
                "mixed precision through mxtpu.amp (amp=True) so the "
                "precision ledgers stay true, or waive with a pragma"))
        return sorted(out, key=lambda f: f.line)


class RawDeserialize(Rule):
    """Disk artifacts reach the process through ONE verified door
    (ISSUE 13): ``mxtpu/cache.py``'s loader checksums and
    key-revalidates every entry before ``pickle.loads`` /
    ``deserialize_and_load`` touch the bytes.  Raw
    ``pickle.load(s)`` / ``marshal.load(s)`` /
    ``serialize_executable.deserialize_and_load`` anywhere else in the
    shipped tree is a silent wrong-executable / arbitrary-code hazard
    the cache module exists to fence.  Waive a deliberate site (an
    in-process round-trip of bytes this process just produced, a
    checkpoint format with its own framing) with
    ``# mxlint: disable=raw-deserialize`` and say why."""

    name = "raw-deserialize"
    _LOADERS = {"pickle.load", "pickle.loads", "cPickle.load",
                "cPickle.loads", "marshal.load", "marshal.loads"}

    def applies(self, ctx: FileCtx) -> bool:
        return super().applies(ctx) and ctx.rel != "mxtpu/cache.py"

    def check(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if d is None:
                continue
            if d in self._LOADERS:
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    f"raw `{d}` on disk bytes outside mxtpu/cache.py "
                    f"— route persisted artifacts through the cache's "
                    f"checksum-verified loader, or waive with a "
                    f"pragma stating why this site is safe"))
            elif d.endswith("deserialize_and_load"):
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    "`deserialize_and_load` outside mxtpu/cache.py — "
                    "loading an unverified executable can silently "
                    "run the WRONG program; only the cache's "
                    "verified loader may revive compiled payloads"))
        return out


# ----------------------------------------------------------------------
# repo-level checks
# ----------------------------------------------------------------------
def readme_drift(root: Path) -> List[Finding]:
    """README knob table must match ``knobs.readme_table()``
    (regenerate with ``python -m tools.mxlint --fix-readme``)."""
    knobs = load_knobs_module()
    readme = root / "README.md"
    if not readme.exists():
        return [Finding("knob-readme-drift", "README.md", 1,
                        "README.md missing")]
    text = readme.read_text()
    begin, end = knobs.TABLE_BEGIN, knobs.TABLE_END
    if begin not in text or end not in text:
        return [Finding(
            "knob-readme-drift", "README.md", 1,
            "README.md lacks the mxlint:knob-table markers — run "
            "`python -m tools.mxlint --fix-readme`")]
    current = text.split(begin, 1)[1].split(end, 1)[0]
    want = knobs.readme_table().split(begin, 1)[1].split(end, 1)[0]
    if current.strip() != want.strip():
        line = text[:text.index(begin)].count("\n") + 1
        return [Finding(
            "knob-readme-drift", "README.md", line,
            "README knob table is stale vs mxtpu/knobs.py — run "
            "`python -m tools.mxlint --fix-readme`",
            snippet="knob-table")]
    return []


def fix_readme(root: Path) -> bool:
    """Rewrite the README table between the markers; returns True when
    the file changed."""
    knobs = load_knobs_module()
    readme = root / "README.md"
    text = readme.read_text()
    begin, end = knobs.TABLE_BEGIN, knobs.TABLE_END
    if begin not in text or end not in text:
        raise SystemExit(
            f"README.md lacks the markers {begin!r} … {end!r}; add "
            f"them where the table should live")
    head = text.split(begin, 1)[0]
    tail = text.split(end, 1)[1]
    new = head + knobs.readme_table() + tail
    if new != text:
        readme.write_text(new)
        return True
    return False


# ----------------------------------------------------------------------
# registry of rules
# ----------------------------------------------------------------------
def file_rules() -> List[Rule]:
    return [RetraceImpureCall(), RetraceTracedBranch(),
            RetraceInlineJit(), RetraceConcretize(), HostSync(),
            LockDiscipline(), KnobRawEnv(), KnobUnregistered(),
            HloRawAssert(), MemHygiene(), ObsRegistry(),
            ThreadHygiene(), DtypeHygiene(), NoAdhocBf16(),
            RawDeserialize()]


def repo_checks(ctxs: Sequence[FileCtx], root: Path) -> List[Finding]:
    return readme_drift(root)
