"""JAX compile-path lint rules: AST analysis + abstract shape probing.

Static analysis of the device hot path — the defects XLA only surfaces
after minutes of tracing (or never surfaces, silently recompiling every
call) are caught here in milliseconds:

- TX-J01 implicit host transfer inside a jitted function: ``np.*`` calls,
  ``.item()`` / ``.tolist()``, or ``float()/int()/bool()`` applied to a
  traced value — each forces a device->host sync per call.
- TX-J02 recompilation hazard: ``jax.jit`` applied inside a loop or a
  plain (non-memoized) function body builds a FRESH jitted callable per
  call, so XLA recompiles every time. The blessed repo idiom — a
  ``functools.lru_cache``'d builder returning ``jax.jit(...)`` — is
  recognized and allowed.
- TX-J03 non-hashable static argument: a list/dict/set passed for a
  parameter the jit declares static — TypeError at trace time, or (for
  a tuple-of-list) a silent cache miss per call.
- TX-J04 float64 creep: float64 dtypes requested inside a jitted
  function — on TPU this means silent f32 downcast (x64 off) or a 2x
  memory/bandwidth tax (x64 on).
- TX-J05 Python control flow on a traced value: ``if``/``while`` on a
  non-static parameter concretizes the tracer -> TracerBoolConversionError
  at trace time, i.e. concrete-shape dependence.
- TX-J06 serving hot path (``serving/`` files only): per-call
  ``jax.jit`` — a trace/compile per REQUEST — or a Python per-row loop
  over ``transform_value``, the exact pattern the compiled ScoringPlan
  exists to replace. The J02 per-call-jit patterns report as J06 (error
  severity) there.
- TX-R01 swallowed backend error (``selector/`` + ``serving/`` files
  only): an ``except Exception`` / bare ``except`` whose body neither
  re-raises nor routes the error through the fault runtime's recovery
  vocabulary (quarantine / classify_error / a recorded fallback /
  maybe_inject) — hides XlaRuntimeErrors, silently degrading searches
  to the slow path (docs/resilience.md).
- TX-R02 silent record drop (``serving/`` files + ``local/scoring.py``
  only): an except handler that drops the current record — a
  ``continue`` out of the scoring loop, or a ``pass``-only body inside
  a loop — without recording a reason (quarantine / telemetry count or
  event / a ``note_*``/``record*`` call / logging). Rows discarded on
  exception with no machine-readable trace are the serving twin of a
  swallowed XlaRuntimeError: traffic silently disappears
  (docs/serving_guardrails.md).
- TX-J07 grid value into a compile key: inside a fit kernel (a function
  with a ``grid`` parameter / a ``fold_grid`` name), a value derived
  from the hyperparameter grid passed for a ``static_argnames``
  parameter of a jitted function, or into an ``lru_cache``'d kernel
  builder — every grid point then keys a fresh XLA program (G x F
  compiles instead of 1). Grid values must flow as TRACED vectors
  (vmapped candidate lanes); only aggregate predicates over the whole
  grid (``any``/``all``/``len``/...) may shape statics, and the taint
  tracking deliberately stops at them and at non-trivial calls so the
  repo's grouped-statics idiom (trees/mlp static shape groups) stays
  legal.
- TX-J09 train hot path (``workflow/`` files only): host feature
  materialization reachable from ``Workflow.train()`` — a direct
  ``.transform_columns(...)`` call (the per-stage host walk the
  compiled PreparePlan replaces; stages with ``transform_arrays``
  kernels should execute fused on device, plans/prepare.py) or a
  Python per-row loop over ``transform_value``. The TX_PREPARE=host
  escape hatch is the ONLY blessed host walk and carries an inline
  suppression so the exemption is visible and reviewable.
- TX-J10 blocking call inside a serving ASYNC handler (``serving/``
  files only): ``time.sleep`` (the loop stalls for every in-flight
  request — ``await asyncio.sleep`` exists), a synchronous device
  materialization (``.block_until_ready()``, ``np.asarray``/
  ``np.array`` on device output), or file I/O (``open``) directly in
  an ``async def`` body. The serving loop (serving/server.py) routes
  ALL blocking work through named executors; an inline blocking call
  in a coroutine wedges the coalescer for every tenant at once.
  Nested SYNC functions inside an async def are exempt — that is
  exactly the run_in_executor idiom.
- TX-R04 torn state-file write (``serving/`` files only): an
  ``open(path, "w"|"a"|...)`` whose target is a LIVE path — not a
  ``*.tmp`` staging file — bypasses the repo's shared atomic writer
  (``observability/store.atomic_write_json``: temp file +
  ``os.replace``). A process killed mid-write (the exact event the
  preemption-tolerance stack exists for, docs/serving_restart.md)
  leaves a torn half-document where a snapshot/fingerprint used to
  be. Paths that mention ``tmp`` (a ``.tmp`` suffix concatenation, a
  ``tmp``-named variable, tempfile machinery) are the sanctioned
  staging idiom and stay legal; reads are untouched.
- TX-R05 unbounded request queue (``serving/`` files only): a bare
  ``collections.deque()`` / ``asyncio.Queue()`` (no ``maxlen=`` /
  ``maxsize=``, or an explicit unbounded ``maxlen=None`` /
  ``maxsize=0``) assigned to a request-queue-shaped name (``*queue*``,
  ``*backlog*``, ``*pending*``). An unbounded lane queue is the
  overload failure mode admission control exists to close
  (docs/admission.md): a burst above capacity grows it without limit —
  first memory, then every queued request's latency. Bound the
  container and shed overflow at the enqueue edge with a
  machine-readable ``retry_after_ms`` answer (serving/admission.py);
  bounded constructions and non-queue names are untouched.
- TX-R07 leaked connection writer (``serving/`` files only): a
  socket / stream writer / transport stored into a dict-like
  container (``self._writers[key] = writer``) in a module with NO
  removal path for that container anywhere — no ``del c[...]``, no
  ``.pop(...)``/``.popitem()``/``.clear()``/``.discard(...)``. Every
  client disconnect then leaks one writer entry (and its socket fd):
  the table only grows, and a long-lived server exhausts fds under
  nothing but ordinary connection churn. The fix is structural — the
  handler's ``finally`` must evict the entry when the connection
  dies (serving/router.py's ``_client_writers`` is the reference
  shape). Stores of non-connection values and containers with any
  observed cleanup call are untouched.
- TX-O01 telemetry/trace emission inside a jitted function body:
  ``telemetry.event(...)``/``telemetry.count(...)``, a tracer span
  enter/exit (``trace.span``/``add_span``/``add_event``), or a
  wall-clock read (``time.time``/``perf_counter``/``monotonic``).
  The body of a jitted function runs at TRACE time — such a call
  records compilation (not execution), fires once per compile instead
  of once per call, and a value derived from it baked into the trace
  forces recompiles. ``compile_time.section`` is deliberately exempt:
  measuring trace cost inside a traced body is its documented job
  (plans/prepare.py per-stage sections).
- TX-J08 implicit replication under ``shard_map``/``pjit``: the body
  function closes over an array-like value from the enclosing scope
  instead of receiving it through ``in_specs``. A closed-over operand
  gets no PartitionSpec, so XLA replicates it IN FULL to every device —
  the fold matrix paid once per chip, silently (the sharded search's
  HBM budget assumes one copy across the ``data`` axis). Arrays must
  enter the body as arguments with explicit specs (``P()`` when
  replication is the intent — then it is visible and reviewable).
  Config scalars (``cfg``/``spec``/``statics``/axis names...) may close
  over freely; the rule keys on array-ish names only.
- TX-T01 numeric literal default for a registered tunable knob outside
  ``tuning/``: assigning a number to one of the registry's blessed
  constant names (``_DEFAULT_TARGET``, ``DEFAULT_MIN_BUCKET``, ...) at
  module/class level, or giving a registered knob PARAMETER (``eta``,
  ``min_fidelity``, ``placement_margin``) a numeric literal default,
  re-introduces a second source of truth the autotuning layer cannot
  govern — ``tx tune --set`` and the cost model would silently stop
  applying to that call path. The single source of truth is
  ``tuning/registry.py``'s ``STATIC_DEFAULTS``; consumers read the
  registry (or default the parameter to ``None`` and resolve through
  ``TuningPolicy``). Files under ``tuning/`` are exempt — that IS the
  registry.
- TX-T02 hardcoded power-of-two bucket math in the bucketing layers
  (``serving/``, ``plans/``, ``tuning/``, ``artifacts/``,
  ``analysis/``): ``1 << n``, ``2 ** n`` with a computed exponent, or
  a ``b *= 2`` / ``b <<= 1`` grow loop re-derives the bucket ladder
  locally. Plans resolve batch sizes through an EXPLICIT lattice now
  (docs/ragged_batching.md) — a tuned non-power-of-two ladder makes
  every local pow2 computation silently wrong. ``plans/common.py``
  (the ``bucket_for``/``pad_rows`` entry points) and
  ``tuning/lattice.py`` (the lattice math itself) are the two files
  where that arithmetic legally lives and are exempt.

Scope discipline keeps the rules precise: J01/J04/J05 only fire INSIDE
functions statically known to be jitted (decorated with ``jax.jit`` or
``functools.partial(jax.jit, ...)``); host-side numpy orchestration code
is untouched. ``abstract_probe`` complements the AST with
``jax.eval_shape`` — tracing a callable with abstract values only, so
host-transfer and concretization defects hidden behind dynamic dispatch
are confirmed without executing a single device instruction.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from .findings import ERROR, WARNING, LintFinding

__all__ = ["lint_source", "lint_file", "abstract_probe"]

#: attribute accesses on a traced value that stay abstract (shape/dtype
#: are static at trace time — reading them is free and safe)
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "at"}

#: np.<fn> calls that are trace-time constants, not host transfers
_NP_SAFE_CALLS = {"iinfo", "finfo", "dtype"}

#: methods that force a device->host transfer / concretization
_HOST_METHODS = {"item", "tolist", "block_until_ready", "to_py"}

_F64_NAMES = {"float64", "f64", "double"}

#: calls that REDUCE over the whole grid — their result is one value
#: per search, not one per grid point, so TX-J07 taint stops there
#: (``use_l1 = bool(np.any(grid[:, 0] * grid[:, 1] > 0))`` is the
#: blessed aggregate-static idiom)
_AGGREGATE_CALLS = {"len", "any", "all", "bool", "max", "min", "sum",
                    "set", "frozenset"}

#: calls that merely re-wrap a sequence — taint flows THROUGH them
#: (``for p in list(grid)``, ``for gi, p in enumerate(grid)``)
_PASSTHROUGH_CALLS = {"list", "tuple", "dict", "enumerate", "zip",
                      "reversed", "sorted", "iter"}

#: TX-J08: free variables of a shard_map/pjit body that LOOK like data
#: arrays (the values whose implicit replication costs HBM per chip).
#: Deliberately name-based: config scalars (cfg/spec/statics/axis
#: names) close over shard bodies legitimately throughout the repo.
import re as _re

_ARRAYISH_FREE = _re.compile(
    r"(?i)^(x|y|w|b|xs|ys|xv|yv|wmat|masks?|grid|labels?|features?|"
    r"rows|cols|data|batch|inputs?|outputs?|onehot|weights?|biases)"
    r"(_[a-z0-9_]+)?$"
    r"|^.*_(mat|matrix|arrays?|st|val|train)$")

#: names that never carry a data array into a shard body (kernel
#: configuration, mesh/axis plumbing, callables)
_SHARD_CONFIG_NAMES = {"mesh", "spec", "cfg", "statics", "axis",
                       "axis_name", "data_ax", "model_ax", "kind",
                       "self", "cls", "fn", "core", "body", "one",
                       "batched"}


# ---------------------------------------------------------------------------
# import/alias resolution
# ---------------------------------------------------------------------------

class _Aliases:
    """Names the module binds to numpy / jax / jax.numpy / functools."""

    def __init__(self):
        self.numpy: Set[str] = set()
        self.jax: Set[str] = set()
        self.jnp: Set[str] = set()
        self.jit: Set[str] = set()        # `from jax import jit [as j]`
        self.partial: Set[str] = set()    # `from functools import partial`
        self.functools: Set[str] = set()
        self.lru: Set[str] = set()        # `from functools import lru_cache`

    @classmethod
    def collect(cls, tree: ast.Module) -> "_Aliases":
        al = cls()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = a.asname or a.name
                    if a.name == "numpy":
                        al.numpy.add(name)
                    elif a.name == "jax":
                        al.jax.add(name)
                    elif a.name == "jax.numpy":
                        al.jnp.add(name)
                    elif a.name == "functools":
                        al.functools.add(name)
            elif isinstance(node, ast.ImportFrom):
                for a in node.names:
                    name = a.asname or a.name
                    if node.module == "jax" and a.name == "jit":
                        al.jit.add(name)
                    elif node.module == "jax" and a.name == "numpy":
                        al.jnp.add(name)
                    elif node.module == "functools":
                        if a.name == "partial":
                            al.partial.add(name)
                        elif a.name in ("lru_cache", "cache"):
                            al.lru.add(name)
        return al

    def is_jax_jit(self, node: ast.AST) -> bool:
        """``jax.jit`` / bare ``jit`` reference."""
        if isinstance(node, ast.Attribute) and node.attr == "jit" \
                and isinstance(node.value, ast.Name) \
                and node.value.id in self.jax:
            return True
        return isinstance(node, ast.Name) and node.id in self.jit

    def is_partial(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name) and node.id in self.partial:
            return True
        return (isinstance(node, ast.Attribute) and node.attr == "partial"
                and isinstance(node.value, ast.Name)
                and node.value.id in self.functools)

    def is_lru_cache(self, node: ast.AST) -> bool:
        target = node.func if isinstance(node, ast.Call) else node
        if isinstance(target, ast.Name) and target.id in self.lru:
            return True
        return (isinstance(target, ast.Attribute)
                and target.attr in ("lru_cache", "cache")
                and isinstance(target.value, ast.Name)
                and target.value.id in self.functools)


def _static_names_from_call(call: ast.Call,
                            fn: Optional[ast.FunctionDef]) -> Set[str]:
    """Parameter names declared static via static_argnames/static_argnums
    keywords of a ``jax.jit`` / ``partial(jax.jit, ...)`` call."""
    static: Set[str] = set()
    params = []
    if fn is not None:
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    for kw in call.keywords:
        v = kw.value
        if kw.arg == "static_argnames":
            vals = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in vals:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    static.add(e.value)
        elif kw.arg == "static_argnums":
            vals = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in vals:
                if isinstance(e, ast.Constant) and isinstance(e.value, int) \
                        and 0 <= e.value < len(params):
                    static.add(params[e.value])
    return static


def _jit_decoration(fn: ast.FunctionDef, al: _Aliases
                    ) -> Optional[Set[str]]:
    """None when ``fn`` is not statically jitted; otherwise the set of
    static parameter names. Recognizes ``@jax.jit``, ``@jit``,
    ``@jax.jit(...)`` and ``@functools.partial(jax.jit, ...)``."""
    for dec in fn.decorator_list:
        if al.is_jax_jit(dec):
            return set()
        if isinstance(dec, ast.Call):
            if al.is_jax_jit(dec.func):
                return _static_names_from_call(dec, fn)
            if al.is_partial(dec.func) and dec.args \
                    and al.is_jax_jit(dec.args[0]):
                return _static_names_from_call(dec, fn)
    return None


# ---------------------------------------------------------------------------
# traced-value reachability inside an expression
# ---------------------------------------------------------------------------

def _mentions_traced(node: ast.AST, traced: Set[str]) -> bool:
    """Does the expression reference a traced name in a way that needs a
    concrete value? Reads of static attributes (``x.shape``...) and
    ``len(x)`` are trace-time constants and don't count."""
    if isinstance(node, ast.Name):
        return node.id in traced
    if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
        return False                       # x.shape / x.dtype: static
    if isinstance(node, ast.Call):
        if isinstance(node.func, ast.Name) and node.func.id == "len":
            return False                   # len(traced) is static
        if isinstance(node.func, ast.Attribute):
            # x.astype(...) etc: the CALL result is still traced, but
            # deciding that needs type inference; the test below treats
            # the receiver as the signal
            return any(_mentions_traced(a, traced)
                       for a in [node.func.value] + list(node.args))
        return any(_mentions_traced(a, traced)
                   for a in list(node.args)
                   + [kw.value for kw in node.keywords])
    if isinstance(node, ast.Compare):
        # `x is None` / `x is not None` never concretizes a tracer
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return False
    return any(_mentions_traced(c, traced)
               for c in ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# the per-file visitor
# ---------------------------------------------------------------------------

def _is_serving_path(path: str) -> bool:
    """serving/ package files get the TX-J06 hot-path rules."""
    import re
    return "serving" in re.split(r"[/\\]", path)


def _is_artifact_path(path: str) -> bool:
    """serving/ and cli/ files get the TX-R06 artifact-bypass rule:
    these trees score saved models, where a direct
    ``ScoringPlan(...).compile()`` ignores the model dir's exported
    AOT executables and pays a cold XLA compile per bucket
    (artifacts/loader.py is the sanctioned entry point)."""
    import re
    parts = re.split(r"[/\\]", path)
    return "serving" in parts or "cli" in parts


def _is_train_path(path: str) -> bool:
    """workflow/ package files get the TX-J09 train-hot-path rule: the
    code ``Workflow.train()`` executes between raw data and the fitted
    model, where host transform_columns walks bypass the compiled
    prepare path (plans/prepare.py)."""
    import re
    return "workflow" in re.split(r"[/\\]", path)


def _is_resilience_path(path: str) -> bool:
    """selector/ and serving/ files get the TX-R01 exception-swallow
    rule: these are the hot paths where a swallowed XlaRuntimeError
    silently degrades a whole search/request instead of being retried,
    quarantined or surfaced."""
    import re
    parts = re.split(r"[/\\]", path)
    return "selector" in parts or "serving" in parts


#: a broad except handler is acceptable when its body does one of
#: these: re-raise, or route the error through the runtime's recovery
#: vocabulary (quarantine/classify/fallback/inject) so the degradation
#: is RECORDED rather than swallowed
_RECOVERY_NAME_PARTS = ("quarantine", "classify", "fallback",
                        "maybe_inject")

#: TX-R02 accepts a wider recording vocabulary than TX-R01: dropping a
#: record is sometimes the right call (malformed row), but the drop
#: must leave a trace — a quarantine reason, a telemetry counter/event,
#: a ``note_*``/``record*`` bookkeeping call, or at least a log line
_DROP_RECORD_NAME_PARTS = _RECOVERY_NAME_PARTS + (
    "record", "note", "count", "event", "warn", "log", "error")

#: TX-R03: the load-bearing attributes of a live cache entry — writing
#: one IN PLACE on an entry you did not just build races every
#: in-flight batch holding a reference to it (and forfeits rollback:
#: there is no previous value to pin). ``self.<attr> = ...`` inside the
#: owning class (entry construction, the PlanCache helpers themselves)
#: stays legal.
_R03_ENTRY_ATTRS = frozenset({"plan", "model", "result_names"})
#: the registries TX-R03 guards against out-of-band subscript writes:
#: mutating another object's ``_entries``/``_overrides``/``_pinned``/
#: ``_loaders`` bypasses swap_entry/rollback/commit's pin bookkeeping
_R03_REGISTRY_ATTRS = frozenset({"_entries", "_loaders",
                                 "_overrides", "_pinned"})


def _is_self_name(node: ast.AST) -> bool:
    return isinstance(node, ast.Name) and node.id == "self"


def _is_tuning_path(path: str) -> bool:
    """tuning/ package files are exempt from TX-T01 — the registry
    itself is where the literal defaults legally live."""
    import re
    return "tuning" in re.split(r"[/\\]", path)


#: the packages whose row/bucket arithmetic TX-T02 polices — the
#: layers a tuned non-pow2 lattice flows through
_T02_PACKAGES = frozenset(
    {"serving", "plans", "tuning", "artifacts", "analysis"})


def _is_bucket_math_path(path: str) -> bool:
    """TX-T02 scope: the bucketing layers, MINUS the two files where
    power-of-two arithmetic legally lives — ``plans/common.py``
    (bucket_for/pad_rows, the entry points everyone should call) and
    ``tuning/lattice.py`` (the lattice/pow2 math itself)."""
    import re
    parts = re.split(r"[/\\]", path)
    if not _T02_PACKAGES & set(parts):
        return False
    if len(parts) >= 2 and (parts[-2], parts[-1]) in (
            ("plans", "common.py"), ("tuning", "lattice.py")):
        return False
    return True


def _tunable_names() -> tuple:
    """(const names, param name -> consumer-package scopes) registered
    in tuning/registry.py — lazy so the lint package imports standalone
    (and so a stubbed registry degrades to 'rule never fires', not an
    ImportError)."""
    try:
        from ..tuning.registry import (TUNABLE_CONST_NAMES,
                                       TUNABLE_PARAM_SCOPES)
        return TUNABLE_CONST_NAMES, TUNABLE_PARAM_SCOPES
    except ImportError:  # pragma: no cover - registry always present
        return frozenset(), {}


def _is_numeric_literal(node: ast.AST) -> bool:
    """A literal number: ``64``, ``1.5``, ``-3``, ``1.0 / 9`` — the
    shapes a hardcoded knob default takes. bools are not numbers here,
    and any Name/Call/Attribute breaks literal-ness (reading the
    registry is exactly the sanctioned fix)."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (int, float)) \
            and not isinstance(node.value, bool)
    if isinstance(node, ast.UnaryOp) \
            and isinstance(node.op, (ast.USub, ast.UAdd)):
        return _is_numeric_literal(node.operand)
    if isinstance(node, ast.BinOp):
        return _is_numeric_literal(node.left) \
            and _is_numeric_literal(node.right)
    return False


def _is_record_drop_path(path: str) -> bool:
    """serving/ files + local/scoring.py get the TX-R02 silent-record-
    drop rule: the code paths rows flow through on their way to or
    from a model."""
    import re
    parts = re.split(r"[/\\]", path)
    return "serving" in parts or (
        len(parts) >= 2 and parts[-2] == "local"
        and parts[-1] == "scoring.py")


def _handler_drops_silently(h: ast.ExceptHandler,
                            in_loop: bool) -> bool:
    """Does the handler drop the current record with no recorded
    reason — a ``continue``, or (inside a loop) a ``pass``-only body —
    and neither re-raise nor call anything from the recording
    vocabulary?"""
    has_continue = any(isinstance(sub, ast.Continue)
                       for sub in ast.walk(h))
    pass_only = in_loop and all(isinstance(s, ast.Pass) for s in h.body)
    if not has_continue and not pass_only:
        return False
    for sub in ast.walk(h):
        if isinstance(sub, ast.Raise):
            return False
        if isinstance(sub, ast.Call):
            fn = sub.func
            name = (fn.id if isinstance(fn, ast.Name)
                    else fn.attr if isinstance(fn, ast.Attribute) else "")
            if any(p in name for p in _DROP_RECORD_NAME_PARTS):
                return False
    return True


def _handler_is_broad(h: ast.ExceptHandler) -> bool:
    """Bare ``except:`` or ``except Exception`` (possibly in a
    tuple)."""
    t = h.type
    if t is None:
        return True
    names = []
    if isinstance(t, ast.Name):
        names = [t.id]
    elif isinstance(t, ast.Tuple):
        names = [e.id for e in t.elts if isinstance(e, ast.Name)]
    return "Exception" in names


def _handler_recovers(h: ast.ExceptHandler) -> bool:
    """Does the handler body re-raise, or call into the recovery
    vocabulary (``quarantine``/``classify_error``/``*fallback*``/
    ``maybe_inject``)?"""
    for sub in ast.walk(h):
        if isinstance(sub, ast.Raise):
            return True
        if isinstance(sub, ast.Call):
            fn = sub.func
            name = (fn.id if isinstance(fn, ast.Name)
                    else fn.attr if isinstance(fn, ast.Attribute) else "")
            if any(p in name for p in _RECOVERY_NAME_PARTS):
                return True
    return False


def _calls_transform_value(node: ast.AST) -> bool:
    """Does the subtree call ``<x>.transform_value(...)``?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) \
                and isinstance(sub.func, ast.Attribute) \
                and sub.func.attr == "transform_value":
            return True
    return False


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str, al: _Aliases):
        self.path = path
        self.serving = _is_serving_path(path)
        self.artifact_path = _is_artifact_path(path)
        self.train_path = _is_train_path(path)
        self.resilience = _is_resilience_path(path)
        self.record_drop = _is_record_drop_path(path)
        #: TX-T01: files under tuning/ may hold the literal defaults
        self.tuning_path = _is_tuning_path(path)
        #: TX-T02: bucketing layers where local pow2 ladder math is
        #: banned (plans/common.py + tuning/lattice.py exempt)
        self.bucket_math_path = _is_bucket_math_path(path)
        self._tunable_consts, self._tunable_params = _tunable_names()
        self.al = al
        self.findings: List[LintFinding] = []
        #: stack of enclosing FunctionDefs, innermost last
        self.fn_stack: List[ast.FunctionDef] = []
        #: TX-J10: directly inside an `async def` body (a nested SYNC
        #: def resets this — that's the run_in_executor idiom)
        self.in_async = False
        #: stack of "inside a loop" flags per function level
        self.loop_depth = 0
        #: when non-None we are inside a jitted function: set of traced
        #: (non-static) parameter names accumulated over nested scopes
        self.jit_ctx: Optional[Set[str]] = None
        self.jit_fn_name = ""
        #: module-level registry: jitted fn name -> static argnames
        self.jitted_statics: Dict[str, Set[str]] = {}
        #: TX-J07: when non-None we are inside a fit-kernel function
        #: (a ``grid`` parameter / ``fold_grid`` name): names tainted
        #: by per-grid-point values
        self.grid_ctx: Optional[Set[str]] = None
        self.grid_fn_name = ""
        #: module-level registry: lru_cache'd builder names (the
        #: memoized jit-builder idiom — their ARGUMENTS are compile
        #: cache keys)
        self.memoized_builders: Set[str] = set()
        #: TX-R07 (module-wide, resolved in :meth:`finalize`):
        #: container name -> first node that stored a connection
        #: writer into it, and the set of containers with ANY
        #: observed removal path
        self._writer_stores: Dict[str, ast.AST] = {}
        self._writer_cleanups: Set[str] = set()

    # -- helpers -----------------------------------------------------------
    def add(self, rule: str, node: ast.AST, message: str,
            severity: str, hint: str = None) -> None:
        self.findings.append(LintFinding(
            rule_id=rule, severity=severity, path=self.path,
            line=getattr(node, "lineno", 0), message=message, hint=hint))

    def _in_memoized_builder(self) -> bool:
        """True when any enclosing function is an lru_cache'd builder —
        the jit-once idiom (build + cache the jitted callable per static
        config)."""
        return any(
            any(self.al.is_lru_cache(d) for d in fn.decorator_list)
            for fn in self.fn_stack)

    # -- TX-J07 grid-taint helpers -----------------------------------------
    def _is_grid_alias(self, v: ast.AST) -> bool:
        """Does this VALUE carry per-grid-point taint through a trivial
        re-wrapping only? Deliberately narrow: taint flows through
        aliases, subscripts and list()/dict()-style re-wraps, but stops
        at aggregates and at any non-trivial call — so the repo's
        grouped-statics idiom (grid -> with_params -> static groups,
        one compile per GROUP) stays untainted, while ``p["max_depth"]``
        of a per-point loop is caught."""
        if self.grid_ctx is None:
            return False
        if isinstance(v, ast.Name):
            return v.id in self.grid_ctx
        if isinstance(v, ast.Subscript):
            return self._is_grid_alias(v.value)
        if isinstance(v, ast.Call):
            fn = v.func
            if isinstance(fn, ast.Name) and fn.id in _PASSTHROUGH_CALLS:
                return any(self._is_grid_alias(a) for a in v.args)
            return False
        if isinstance(v, (ast.ListComp, ast.GeneratorExp)):
            return any(self._is_grid_alias(g.iter) for g in v.generators)
        if isinstance(v, ast.BoolOp):      # list(grid) or [{}]
            return any(self._is_grid_alias(x) for x in v.values)
        return False

    def _mentions_grid(self, node: ast.AST) -> bool:
        """Does this CALL-SITE expression reference a tainted name —
        descending through arithmetic and non-aggregate calls, stopping
        at whole-grid aggregates?"""
        if isinstance(node, ast.Name):
            return node.id in self.grid_ctx
        if isinstance(node, ast.Call):
            fn = node.func
            name = (fn.id if isinstance(fn, ast.Name)
                    else fn.attr if isinstance(fn, ast.Attribute) else "")
            if name in _AGGREGATE_CALLS:
                return False
            parts = list(node.args) + [kw.value for kw in node.keywords]
            if isinstance(fn, ast.Attribute):
                parts.append(fn.value)     # p.get(...) taints via p
            return any(self._mentions_grid(p) for p in parts)
        return any(self._mentions_grid(c)
                   for c in ast.iter_child_nodes(node))

    def _taint_targets(self, target: ast.AST) -> None:
        if isinstance(target, ast.Name):
            self.grid_ctx.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._taint_targets(elt)

    @staticmethod
    def _is_grid_kernel(node: ast.FunctionDef) -> bool:
        params = {a.arg for a in (node.args.posonlyargs + node.args.args
                                  + node.args.kwonlyargs)}
        return "grid" in params or "fold_grid" in node.name

    # -- TX-T01 ------------------------------------------------------------
    def _check_tunable_const(self, target: ast.AST,
                             value: Optional[ast.AST]) -> None:
        """Module/class-level ``<BLESSED_CONST> = <number>`` outside
        tuning/ — a second source of truth for a registered knob."""
        if self.tuning_path or self.fn_stack or value is None:
            return
        if isinstance(target, ast.Name) \
                and target.id in self._tunable_consts \
                and _is_numeric_literal(value):
            self.add(
                "TX-T01", target,
                f"numeric literal default for tunable knob constant "
                f"{target.id!r} outside tuning/ — tx tune overrides "
                f"and the cost model no longer govern this value",
                ERROR,
                hint="read it from the registry: from ..tuning.registry "
                     "import STATIC_DEFAULTS (tuning/registry.py is the "
                     "single source of truth)")

    def _check_tunable_defaults(self, node: ast.FunctionDef) -> None:
        """``def f(eta=3)`` in the knob's consumer package: a
        registered knob parameter with a hardcoded numeric default
        bypasses the TuningPolicy resolution path. Scope discipline:
        the spelling only means the knob in its consumer layer
        (``eta`` in models/trees/ is a GBT learning rate, legal)."""
        if self.tuning_path:
            return
        import re
        parts = set(re.split(r"[/\\]", self.path))
        pos = node.args.posonlyargs + node.args.args
        pairs = list(zip(pos[len(pos) - len(node.args.defaults):],
                         node.args.defaults))
        pairs += [(a, d) for a, d in zip(node.args.kwonlyargs,
                                         node.args.kw_defaults)
                  if d is not None]
        for arg, default in pairs:
            if parts & self._tunable_params.get(arg.arg, frozenset()) \
                    and _is_numeric_literal(default):
                self.add(
                    "TX-T01", default,
                    f"parameter {arg.arg!r} of {node.name!r} is a "
                    f"registered tunable knob with a numeric literal "
                    f"default — callers that omit it silently pin the "
                    f"knob, so tx tune overrides and the cost model "
                    f"never apply",
                    ERROR,
                    hint="default it to None and resolve through "
                         "TuningPolicy (or read tuning/registry.py's "
                         "STATIC_DEFAULTS)")

    # -- function defs -----------------------------------------------------
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._check_tunable_defaults(node)
        statics = _jit_decoration(node, self.al)
        outer_ctx, outer_name = self.jit_ctx, self.jit_fn_name
        outer_grid, outer_grid_name = self.grid_ctx, self.grid_fn_name
        if self._is_grid_kernel(node):
            self.grid_ctx = {"grid"}
            self.grid_fn_name = node.name
        elif self.grid_ctx is None:
            # a nested helper outside any grid kernel resets nothing;
            # inside one, the enclosing taint set stays visible
            self.grid_ctx = None
        outer_loops = self.loop_depth
        if statics is not None:
            # a jitted function: params minus statics are traced values
            if not self.fn_stack:
                self.jitted_statics[node.name] = statics
            elif not self._in_memoized_builder():
                self.add(
                    "TX-J02", node,
                    f"@jit function {node.name!r} is (re)defined per call "
                    f"of {self.fn_stack[-1].name!r} — every call builds a "
                    f"fresh jitted callable and recompiles",
                    WARNING,
                    hint="hoist the @jit function to module level, or "
                         "memoize the builder with functools.lru_cache")
            params = {a.arg for a in (node.args.posonlyargs + node.args.args
                                      + node.args.kwonlyargs)}
            self.jit_ctx = (params - statics) | (outer_ctx or set())
            self.jit_fn_name = node.name
        elif self.jit_ctx is not None:
            # nested helper inside a jit body: its params are traced too
            # (they receive traced values from scan/vmap/call sites)
            params = {a.arg for a in (node.args.posonlyargs + node.args.args
                                      + node.args.kwonlyargs)}
            self.jit_ctx = self.jit_ctx | params
        self.fn_stack.append(node)
        self.loop_depth = 0
        outer_async = self.in_async
        self.in_async = isinstance(node, ast.AsyncFunctionDef)
        self.generic_visit(node)
        self.in_async = outer_async
        self.fn_stack.pop()
        self.loop_depth = outer_loops
        self.jit_ctx, self.jit_fn_name = outer_ctx, outer_name
        self.grid_ctx, self.grid_fn_name = outer_grid, outer_grid_name

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Await(self, node: ast.Await) -> None:
        # an awaited call is by definition not a BLOCKING call (e.g.
        # `await sleep(...)` from asyncio) — mark it so TX-J10 skips it
        setattr(node.value, "_tx_awaited", True)
        self.generic_visit(node)

    # -- loops -------------------------------------------------------------
    def visit_For(self, node: ast.For) -> None:
        self._check_serving_row_loop(node)
        if self.grid_ctx is not None and self._is_grid_alias(node.iter):
            # `for p in grid:` / `for gi, p in enumerate(grid):` —
            # the loop variable is one grid point
            self._taint_targets(node.target)
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    def _check_serving_row_loop(self, node) -> None:
        # TX-J06: per-row transform_value loops have no place in
        # serving code — that is exactly the Python hot loop the
        # compiled ScoringPlan replaces (batch through
        # transform_columns / transform_arrays instead)
        if self.serving and _calls_transform_value(node):
            self.add(
                "TX-J06", node,
                "Python loop over transform_value in serving code — "
                "per-row scoring instead of one batched/compiled "
                "program",
                ERROR,
                hint="route the batch through ScoringPlan (or at least "
                     "transform_columns); transform_value is the "
                     "single-record edge only")
        # TX-J09: the train-time twin — a per-row transform_value loop
        # in the workflow executor is the hot loop the compiled
        # PreparePlan replaces
        if self.train_path and _calls_transform_value(node):
            self.add(
                "TX-J09", node,
                "Python loop over transform_value in the train hot "
                "path — per-row feature materialization instead of "
                "the compiled prepare program",
                ERROR,
                hint="route prepare through PreparePlan "
                     "(plans/prepare.py); transform_value is the "
                     "single-record edge only")

    def visit_ListComp(self, node: ast.ListComp) -> None:
        self._check_serving_row_loop(node)
        self._taint_comprehension(node)
        self.generic_visit(node)

    def _taint_comprehension(self, node) -> None:
        # `[kern(..., p[k]) for p in grid]` — comprehension targets
        # carry per-grid-point taint exactly like for-loop targets
        if self.grid_ctx is None:
            return
        for gen in node.generators:
            if self._is_grid_alias(gen.iter):
                self._taint_targets(gen.target)

    def visit_SetComp(self, node: ast.SetComp) -> None:
        self._taint_comprehension(node)
        self.generic_visit(node)

    def visit_DictComp(self, node: ast.DictComp) -> None:
        self._taint_comprehension(node)
        self.generic_visit(node)

    def visit_GeneratorExp(self, node: ast.GeneratorExp) -> None:
        self._taint_comprehension(node)
        self.generic_visit(node)

    def visit_Try(self, node: ast.Try) -> None:
        # TX-R01: a broad except in a selector/serving hot path that
        # swallows the error (no re-raise, no quarantine/classify/
        # fallback routing) hides XlaRuntimeErrors — real kernel bugs
        # silently degrade every search to the slow path (the exact
        # defect r4's satellite fixed at selector/validator.py:138)
        if self.resilience:
            for h in node.handlers:
                if _handler_is_broad(h) and not _handler_recovers(h):
                    what = ("bare except" if h.type is None
                            else "except Exception")
                    self.add(
                        "TX-R01", h,
                        f"{what} in a selector/serving hot path "
                        f"swallows backend errors (XlaRuntimeError "
                        f"included) without re-raise, quarantine or a "
                        f"recorded fallback",
                        ERROR,
                        hint="narrow the except, re-raise classified "
                             "bugs (runtime.errors.classify_error), or "
                             "route the family through "
                             "RuntimeContext.quarantine / a recorded "
                             "fallback reason")
        # TX-R02: a serving-path handler that drops the current record
        # (continue / pass-only inside a loop) without recording WHY —
        # rows vanishing from scored traffic with no quarantine reason,
        # no counter, no log line (docs/serving_guardrails.md)
        if self.record_drop:
            for h in node.handlers:
                if _handler_drops_silently(h, in_loop=self.loop_depth > 0):
                    self.add(
                        "TX-R02", h,
                        "record dropped on exception with no recorded "
                        "reason in a serving path (silent "
                        "continue/pass) — scored traffic shrinks "
                        "invisibly",
                        ERROR,
                        hint="quarantine the row with a "
                             "machine-readable reason (serving/guard"
                             ".py GuardReason), bump a telemetry "
                             "counter/event, or at minimum log the "
                             "drop before skipping")
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._check_control_flow(node)
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    def visit_If(self, node: ast.If) -> None:
        self._check_control_flow(node)
        self.generic_visit(node)

    def _check_control_flow(self, node) -> None:
        # TX-J05: Python branching on a traced value inside jit
        if self.jit_ctx is None:
            return
        if _mentions_traced(node.test, self.jit_ctx):
            kind = "while" if isinstance(node, ast.While) else "if"
            self.add(
                "TX-J05", node,
                f"`{kind}` on a traced value inside jitted "
                f"{self.jit_fn_name!r} — concretizes the tracer "
                f"(TracerBoolConversionError at trace time)",
                ERROR,
                hint="use jnp.where / lax.cond / lax.while_loop, or "
                     "declare the parameter static via static_argnames")

    # -- TX-J08: shard_map/pjit closure analysis ---------------------------
    @staticmethod
    def _is_shard_call(fn: ast.AST) -> bool:
        name = (fn.id if isinstance(fn, ast.Name)
                else fn.attr if isinstance(fn, ast.Attribute) else "")
        return name in ("shard_map", "pjit")

    def _resolve_local_funcdef(self, name: str):
        """The FunctionDef a shard_map call's first argument names,
        searched through the enclosing function bodies (the repo's
        kernel-builder idiom defines the shard body locally)."""
        for fn in reversed(self.fn_stack):
            for sub in ast.walk(fn):
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)) \
                        and sub.name == name:
                    return sub
        return None

    @staticmethod
    def _free_names(body: ast.AST) -> Set[str]:
        """Names a function body loads but never binds — its closure.
        Bound: its own (and nested) params, assignment/for/
        comprehension targets, nested def names."""
        bound: Set[str] = set()
        loads: Set[str] = set()
        for sub in ast.walk(body):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                if not isinstance(sub, ast.Lambda):
                    bound.add(sub.name)
                a = sub.args
                bound.update(p.arg for p in
                             a.posonlyargs + a.args + a.kwonlyargs)
                if a.vararg:
                    bound.add(a.vararg.arg)
                if a.kwarg:
                    bound.add(a.kwarg.arg)
            elif isinstance(sub, ast.Name):
                if isinstance(sub.ctx, ast.Store):
                    bound.add(sub.id)
                elif isinstance(sub.ctx, ast.Load):
                    loads.add(sub.id)
        return loads - bound

    def _check_shard_closure(self, node: ast.Call) -> None:
        """TX-J08: a shard_map/pjit body closing over an array-like
        value — no PartitionSpec, so XLA replicates it in full to
        every device. Arrays must enter through in_specs (P() when
        replication is intended — explicit and reviewable)."""
        if not self._is_shard_call(node.func) or not node.args:
            return
        target = node.args[0]
        if isinstance(target, ast.Name):
            body = self._resolve_local_funcdef(target.id)
        elif isinstance(target, ast.Lambda):
            body = target
        else:
            body = None
        if body is None:
            return
        where = (f" (in {self.fn_stack[-1].name!r})"
                 if self.fn_stack else "")
        for free in sorted(self._free_names(body)):
            # module CONSTANTS are config, not data — but a single
            # capital letter (X, the feature matrix) is data
            if free in _SHARD_CONFIG_NAMES \
                    or (len(free) > 1 and free.isupper()) \
                    or free in self.al.jax | self.al.jnp | self.al.numpy:
                continue
            if not _ARRAYISH_FREE.match(free):
                continue
            self.add(
                "TX-J08", node,
                f"shard_map/pjit body closes over array-like "
                f"{free!r} from the enclosing scope{where} — the "
                f"operand has no PartitionSpec, so XLA replicates it "
                f"IN FULL to every device (a fold matrix paid once "
                f"per chip)",
                WARNING,
                hint="pass it as a body argument with an explicit "
                     "entry in in_specs — P('data') to shard rows, "
                     "P() when replication is genuinely intended")

    # -- TX-J10: blocking calls in serving async handlers ------------------
    def _check_async_blocking(self, node: ast.Call) -> None:
        """Inside an ``async def`` in a serving/ file, a blocking call
        stalls the event loop — every queued request of every tenant
        waits behind it. The serving loop's contract is that blocking
        work runs in named executors (serving/server.py)."""
        if getattr(node, "_tx_awaited", False):
            return
        where = (f" in async handler {self.fn_stack[-1].name!r}"
                 if self.fn_stack else "")
        fn = node.func
        if isinstance(fn, ast.Attribute):
            root = fn.value
            if fn.attr == "sleep" and isinstance(root, ast.Name) \
                    and root.id == "time":
                self.add(
                    "TX-J10", node,
                    f"blocking time.sleep(...){where} — the serving "
                    f"event loop (and every in-flight request) stalls "
                    f"for the duration",
                    ERROR,
                    hint="await asyncio.sleep(...) instead")
            elif fn.attr == "block_until_ready":
                self.add(
                    "TX-J10", node,
                    f"synchronous device sync .block_until_ready()"
                    f"{where} — blocks the event loop on device "
                    f"completion",
                    ERROR,
                    hint="submit the dispatch to an executor "
                         "(loop.run_in_executor) and await it")
            elif isinstance(root, ast.Name) and root.id in self.al.numpy \
                    and fn.attr in ("asarray", "array", "concatenate"):
                self.add(
                    "TX-J10", node,
                    f"np.{fn.attr}(...) host materialization{where} — "
                    f"a device-output copy (and a blocking sync) on "
                    f"the event loop",
                    ERROR,
                    hint="run host encode/materialization in an "
                         "executor (the serving loop's encode pool "
                         "idiom, serving/server.py)")
        elif isinstance(fn, ast.Name):
            if fn.id == "open":
                self.add(
                    "TX-J10", node,
                    f"file I/O (open){where} — disk latency on the "
                    f"serving event loop",
                    ERROR,
                    hint="do file I/O in an executor, or outside the "
                         "async hot path")
            elif fn.id == "sleep":
                self.add(
                    "TX-J10", node,
                    f"blocking sleep(...){where} (un-awaited, so this "
                    f"is time.sleep, not asyncio's)",
                    ERROR,
                    hint="await asyncio.sleep(...) instead")

    # -- TX-R04: torn state-file writes in serving/ ------------------------
    _WRITE_MODES = ("w", "a", "x")

    @staticmethod
    def _mentions_tmp(expr: ast.AST) -> bool:
        """True when the path expression's AST carries a tmp marker —
        a ``tmp``-named variable, a ``.tmp``/tempfile attribute, or a
        string constant containing ``tmp``. That is the sanctioned
        staging idiom (write ``path + ".tmp"``, then ``os.replace``):
        a torn temp file is harmless, the live path flips atomically."""
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Name) and "tmp" in sub.id.lower():
                return True
            if isinstance(sub, ast.Attribute) \
                    and "tmp" in sub.attr.lower():
                return True
            if isinstance(sub, ast.Constant) \
                    and isinstance(sub.value, str) \
                    and "tmp" in sub.value.lower():
                return True
        return False

    def _check_state_file_write(self, node: ast.Call) -> None:
        """A bare ``open(path, "w")`` to a live path in serving/ code
        is a torn-state hazard: kill the process mid-write (the exact
        event the preemption stack exists for) and the snapshot or
        fingerprint file it was replacing is now half a JSON document.
        The shared writer (observability/store.atomic_write_json)
        stages to ``*.tmp`` and ``os.replace``s — the live path is
        always either the old doc or the new one, never a torn one."""
        fn = node.func
        if not (isinstance(fn, ast.Name) and fn.id == "open"):
            return
        if not node.args:
            return
        mode: Optional[str] = None
        if len(node.args) >= 2 and isinstance(node.args[1], ast.Constant) \
                and isinstance(node.args[1].value, str):
            mode = node.args[1].value
        for kw in node.keywords:
            if kw.arg == "mode" and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, str):
                mode = kw.value.value
        if mode is None or not any(m in mode for m in self._WRITE_MODES):
            return  # read (or unknown) mode: not a state write
        if self._mentions_tmp(node.args[0]):
            return  # staging file for an atomic replace — the idiom
        where = (f" in {self.fn_stack[-1].name!r}"
                 if self.fn_stack else "")
        self.add(
            "TX-R04", node,
            f"state-file write open(..., {mode!r}){where} targets a "
            f"live path — a process killed mid-write (preemption, "
            f"OOM, supervisor restart) leaves a TORN document where "
            f"a readable one used to be",
            ERROR,
            hint="write through observability.store.atomic_write_json "
                 "(stages to *.tmp, then os.replace — the live path "
                 "is never half-written)")

    # -- TX-R06: AOT-artifact-loader bypass in serving//cli/ ---------------
    def _check_plan_compile_bypass(self, node: ast.Call) -> None:
        """``ScoringPlan(...).compile()`` chained directly in serving/
        or cli/ code ignores the saved model's exported AOT executables
        (docs/aot_artifacts.md): the serve process pays a cold XLA
        compile per bucket that ``save_model`` already paid for it.
        ``artifacts.loader.load_or_compile`` is the one sanctioned
        constructor — it attaches the artifacts when the validity key
        matches and falls back LOUDLY (counted) when it doesn't."""
        fn = node.func
        if not (isinstance(fn, ast.Attribute) and fn.attr == "compile"):
            return
        inner = fn.value
        if not isinstance(inner, ast.Call):
            return
        ctor = inner.func
        name = None
        if isinstance(ctor, ast.Name):
            name = ctor.id
        elif isinstance(ctor, ast.Attribute):
            name = ctor.attr
        if name != "ScoringPlan":
            return
        where = (f" in {self.fn_stack[-1].name!r}"
                 if self.fn_stack else "")
        self.add(
            "TX-R06", node,
            f"ScoringPlan(...).compile(){where} bypasses the AOT "
            f"artifact loader — a saved model's exported executables "
            f"are ignored and every bucket pays a cold in-band XLA "
            f"compile",
            ERROR,
            hint="route through artifacts.loader.load_or_compile "
                 "(loads the model dir's serialized executables, "
                 "counted loud fallback to live compile otherwise)")

    # -- TX-R05: unbounded request queues in serving/ ----------------------
    _QUEUE_NAME_HINTS = ("queue", "backlog", "pending")

    @staticmethod
    def _queueish_name(target: ast.AST) -> Optional[str]:
        """The request-queue-shaped name a store targets, or None —
        a plain name or attribute (``self.queue = ...``) whose
        lowercase spelling mentions queue/backlog/pending."""
        name = None
        if isinstance(target, ast.Name):
            name = target.id
        elif isinstance(target, ast.Attribute):
            name = target.attr
        if name is None:
            return None
        low = name.lower()
        return name if any(h in low for h in
                           _Visitor._QUEUE_NAME_HINTS) else None

    def _check_unbounded_queue(self, targets, value) -> None:
        """TX-R05: a bare ``deque()``/``Queue()`` bound to a request-
        queue name in serving/ grows without limit under overload —
        the exact failure mode the admission edge exists to close
        (docs/admission.md). Bounded constructions (``maxlen=``, a
        positive ``maxsize=``) pass."""
        if not isinstance(value, ast.Call):
            return
        fn = value.func
        ctor = fn.id if isinstance(fn, ast.Name) else (
            fn.attr if isinstance(fn, ast.Attribute) else None)
        if ctor == "deque":
            # deque(iterable, maxlen) — second positional IS the bound
            cap = value.args[1] if len(value.args) >= 2 else None
            for kw in value.keywords:
                if kw.arg == "maxlen":
                    cap = kw.value
            unbounded = cap is None or (
                isinstance(cap, ast.Constant) and cap.value is None)
        elif ctor == "Queue":
            # asyncio.Queue(maxsize=0) and Queue() are unbounded
            cap = value.args[0] if value.args else None
            for kw in value.keywords:
                if kw.arg == "maxsize":
                    cap = kw.value
            unbounded = cap is None or (
                isinstance(cap, ast.Constant) and cap.value in (0, None))
        else:
            return
        if not unbounded:
            return
        for target in targets:
            name = self._queueish_name(target)
            if name is None:
                continue
            where = (f" in {self.fn_stack[-1].name!r}"
                     if self.fn_stack else "")
            self.add(
                "TX-R05", value,
                f"unbounded {ctor}() assigned to request queue "
                f"{name!r}{where} — under overload it grows without "
                f"limit: first memory, then every queued request's "
                f"latency (no backpressure ever fires)",
                ERROR,
                hint="bound it (collections.deque(maxlen=...) / "
                     "asyncio.Queue(maxsize=...)) and shed overflow "
                     "at the admission edge with a retry_after_ms "
                     "answer (serving/admission.py)")
            return

    # -- TX-R07: leaked connection writers in serving/ ---------------------
    _WRITER_NAME_HINTS = ("writer", "sock", "conn", "transport",
                          "stream")

    @staticmethod
    def _r07_container_name(node: ast.AST) -> Optional[str]:
        """The name of a dict-like container — a plain name or a
        ``self.<attr>``; anything else is out of scope."""
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute) and _is_self_name(node.value):
            return node.attr
        return None

    @classmethod
    def _r07_writerish(cls, value: ast.AST) -> bool:
        """Is the stored VALUE connection-shaped — a name/attribute
        (or a tuple holding one) whose spelling mentions a writer/
        socket/transport? Deliberately shallow: a call result like
        ``make_stream_handler(...)`` is not tracked (too many false
        positives), a plain ``writer`` variable is."""
        if isinstance(value, ast.Tuple):
            return any(cls._r07_writerish(e) for e in value.elts)
        name = None
        if isinstance(value, ast.Name):
            name = value.id
        elif isinstance(value, ast.Attribute):
            name = value.attr
        return bool(name) and any(h in name.lower()
                                  for h in cls._WRITER_NAME_HINTS)

    def _check_writer_store(self, targets, value) -> None:
        for target in targets:
            if not isinstance(target, ast.Subscript):
                continue
            cname = self._r07_container_name(target.value)
            if cname is not None and self._r07_writerish(value):
                self._writer_stores.setdefault(cname, target)

    def _check_writer_cleanup_call(self, node: ast.Call) -> None:
        fn = node.func
        if isinstance(fn, ast.Attribute) \
                and fn.attr in ("pop", "popitem", "clear", "discard"):
            cname = self._r07_container_name(fn.value)
            if cname is not None:
                self._writer_cleanups.add(cname)

    def finalize(self) -> None:
        """Module-wide verdicts that need the WHOLE tree seen first.
        TX-R07: every container that received a connection-writer
        store but shows no removal path anywhere in the module leaks
        one entry (and one socket fd) per client disconnect."""
        for cname, node in sorted(self._writer_stores.items(),
                                  key=lambda kv: kv[1].lineno):
            if cname in self._writer_cleanups:
                continue
            self.add(
                "TX-R07", node,
                f"connection writer stored in {cname!r} with no "
                f"disconnect-cleanup path anywhere in this module — "
                f"every client disconnect leaks the entry (and its "
                f"socket fd); the table only grows until the process "
                f"runs out of file descriptors",
                ERROR,
                hint=f"evict the entry when the connection dies: "
                     f"`finally: {cname}.pop(key, None)` in the "
                     f"connection handler (see FleetRouter.handle, "
                     f"serving/router.py)")

    # -- TX-O01: telemetry/trace emission inside a jitted body -------------
    _CLOCK_ATTRS = {"time", "perf_counter", "monotonic", "time_ns",
                    "perf_counter_ns", "monotonic_ns"}
    _TELEMETRY_ATTRS = {"event", "count", "note_dispatch"}
    _TRACER_ATTRS = {"span", "add_span", "add_event"}

    def _check_traced_telemetry(self, node: ast.Call) -> None:
        """Inside a jitted function the body executes once per TRACE:
        a telemetry counter/event, a tracer span, or a wall-clock read
        there records compile-time behavior as if it were run-time —
        and a changing value baked into the trace recompiles. Emit
        telemetry AROUND the dispatch, never inside the traced body.
        (``compile_time.section`` is exempt: measuring trace cost
        inside the body is exactly its job.)"""
        fn = node.func
        if not isinstance(fn, ast.Attribute) \
                or not isinstance(fn.value, ast.Name):
            return
        root, attr = fn.value.id, fn.attr
        what = None
        if root == "time" and attr in self._CLOCK_ATTRS:
            what = (f"wall-clock read time.{attr}() — measures trace "
                    f"time once per compile, not run time per call")
        elif "telemetry" in root.lower() \
                and attr in self._TELEMETRY_ATTRS:
            what = (f"telemetry emission {root}.{attr}(...) — fires "
                    f"once per COMPILE, not once per call")
        elif root in ("trace", "_trace") and attr in self._TRACER_ATTRS:
            what = (f"tracer call {root}.{attr}(...) — a span opened "
                    f"inside a traced body records tracing, not "
                    f"execution")
        if what is not None:
            self.add(
                "TX-O01", node,
                f"{what} (inside jitted {self.jit_fn_name!r})",
                ERROR,
                hint="move the telemetry/clock to the host code "
                     "around the jitted call; compile_time.section is "
                     "the blessed probe for trace-time cost")

    # -- calls -------------------------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        al = self.al
        # TX-J10: blocking calls inside serving async handlers --------------
        if self.serving and self.in_async:
            self._check_async_blocking(node)
        # TX-R04: torn state-file writes anywhere under serving/ ------------
        if self.serving:
            self._check_state_file_write(node)
            # TX-R07: any pop/clear on a container counts as a
            # disconnect-cleanup path for that container
            self._check_writer_cleanup_call(node)
        # TX-R06: AOT-artifact-loader bypass in serving//cli/ ----------------
        if self.artifact_path:
            self._check_plan_compile_bypass(node)
        # TX-O01: telemetry/trace/clock inside a jitted body ----------------
        if self.jit_ctx is not None:
            self._check_traced_telemetry(node)
        # TX-J08: shard_map/pjit closing over unsharded arrays --------------
        self._check_shard_closure(node)
        # TX-J09: host materialization in the train hot path ----------------
        if self.train_path and isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("transform_columns",
                                       "transform_dataset"):
            self.add(
                "TX-J09", node,
                f"host {node.func.attr} walk in the train hot path — "
                f"stages with transform_arrays kernels should execute "
                f"fused on device via the compiled prepare plan",
                WARNING,
                hint="route prepare through PreparePlan "
                     "(plans/prepare.py); the TX_PREPARE=host escape "
                     "hatch is the only blessed host walk and must "
                     "carry an inline suppression")
        # TX-J02 (TX-J06 inside serving/): jax.jit applied at call time ----
        if al.is_jax_jit(node.func):
            per_call_rule = "TX-J06" if self.serving else "TX-J02"
            if self.loop_depth > 0:
                self.add(
                    per_call_rule, node,
                    "jax.jit(...) called inside a loop — a fresh jitted "
                    "callable (and a full XLA recompile) per iteration",
                    ERROR,
                    hint="hoist the jit call out of the loop; the loop "
                         "should call ONE jitted function")
            elif self.fn_stack and not self._in_memoized_builder():
                self.add(
                    per_call_rule, node,
                    f"jax.jit(...) called per invocation of "
                    f"{self.fn_stack[-1].name!r} — the returned callable "
                    f"is rebuilt (and recompiled) every call",
                    ERROR if self.serving else WARNING,
                    hint="compile once per plan/model and cache the "
                         "jitted callable (serving must never pay a "
                         "per-request trace)" if self.serving else
                         "decorate the enclosing builder with "
                         "functools.lru_cache (the memoized-builder "
                         "idiom) or jit once at module level")
            # register module-level `name = jax.jit(fn, static_...)`
        # TX-J03: non-hashable static args at a call site ------------------
        if isinstance(node.func, ast.Name) \
                and node.func.id in self.jitted_statics:
            statics = self.jitted_statics[node.func.id]
            for kw in node.keywords:
                if kw.arg in statics and isinstance(
                        kw.value, (ast.List, ast.Dict, ast.Set,
                                   ast.ListComp, ast.DictComp,
                                   ast.SetComp, ast.GeneratorExp)):
                    kind = type(kw.value).__name__.lower()
                    self.add(
                        "TX-J03", node,
                        f"static argument {kw.arg!r} of jitted "
                        f"{node.func.id!r} receives a non-hashable "
                        f"{kind} — TypeError at trace time",
                        ERROR,
                        hint="pass a tuple (hashable) instead; static "
                             "args key the compilation cache")
        # TX-J07: grid values flowing into compile cache keys --------------
        if self.grid_ctx is not None and isinstance(node.func, ast.Name):
            callee = node.func.id
            if callee in self.jitted_statics:
                statics = self.jitted_statics[callee]
                for kw in node.keywords:
                    if kw.arg in statics and self._mentions_grid(kw.value):
                        self.add(
                            "TX-J07", node,
                            f"grid-derived value reaches static "
                            f"argument {kw.arg!r} of jitted {callee!r} "
                            f"inside {self.grid_fn_name!r} — one XLA "
                            f"compile per grid point (G x F programs "
                            f"instead of 1)",
                            WARNING,
                            hint="make the hyperparameter a traced "
                                 "array and vmap the candidate axis; "
                                 "only whole-grid aggregates (any/all/"
                                 "len) may shape statics")
            if callee in self.memoized_builders:
                parts = list(node.args) + [kw.value
                                           for kw in node.keywords]
                if any(self._mentions_grid(p) for p in parts):
                    self.add(
                        "TX-J07", node,
                        f"grid-derived value keys the memoized kernel "
                        f"builder {callee!r} inside "
                        f"{self.grid_fn_name!r} — a fresh jitted "
                        f"program per grid point (G x F compiles "
                        f"instead of 1)",
                        WARNING,
                        hint="key the builder by family config only; "
                             "pass grid values as traced vmapped "
                             "vectors into ONE kernel")
        # TX-J01: host transfers inside jit --------------------------------
        if self.jit_ctx is not None:
            self._check_host_transfer(node)
        # TX-J04: float64 creep inside jit ---------------------------------
        # Only dtype REQUESTS count (dtype= kwarg, .astype(f64),
        # jnp.float64(x), positional dtype of a jnp/np constructor) — a
        # `x.dtype == jnp.float64` comparison is a guard, not creep.
        if self.jit_ctx is not None:
            f64_args = [kw.value for kw in node.keywords
                        if kw.arg == "dtype" and self._is_f64(kw.value)]
            fn = node.func
            is_cast = (isinstance(fn, ast.Attribute)
                       and fn.attr == "astype") or self._is_f64(fn)
            is_array_ctor = (isinstance(fn, ast.Attribute)
                             and isinstance(fn.value, ast.Name)
                             and fn.value.id in (self.al.jnp
                                                 | self.al.numpy))
            if is_cast or is_array_ctor:
                f64_args += [a for a in node.args if self._is_f64(a)]
            if self._is_f64(fn):
                f64_args.append(fn)
            if f64_args:
                self.add(
                    "TX-J04", node,
                    f"float64 dtype requested inside jitted "
                    f"{self.jit_fn_name!r}",
                    WARNING,
                    hint="TPUs execute f32/bf16; with x64 disabled this "
                         "silently downcasts, with x64 enabled it "
                         "doubles memory traffic — use float32 or an "
                         "explicit bf16 policy")
        self.generic_visit(node)

    def _is_f64(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Constant):
            return node.value in _F64_NAMES
        if isinstance(node, ast.Attribute):
            return node.attr in _F64_NAMES
        return False

    def _check_host_transfer(self, node: ast.Call) -> None:
        fn = node.func
        if isinstance(fn, ast.Attribute):
            root = fn.value
            # np.<anything>(...) — numpy executes on host; feeding it a
            # tracer raises, feeding it a device array syncs + copies
            if isinstance(root, ast.Name) and root.id in self.al.numpy \
                    and fn.attr not in _NP_SAFE_CALLS:
                self.add(
                    "TX-J01", node,
                    f"numpy call np.{fn.attr}(...) inside jitted "
                    f"{self.jit_fn_name!r} — numpy executes on the host "
                    f"(TracerArrayConversionError or an implicit "
                    f"device->host transfer)",
                    ERROR,
                    hint=f"use jnp.{fn.attr} (or a lax primitive) so the "
                         f"op stays in the XLA program")
            # chained module: np.linalg.solve etc.
            elif isinstance(root, ast.Attribute) \
                    and isinstance(root.value, ast.Name) \
                    and root.value.id in self.al.numpy:
                self.add(
                    "TX-J01", node,
                    f"numpy call np.{root.attr}.{fn.attr}(...) inside "
                    f"jitted {self.jit_fn_name!r} — host execution",
                    ERROR,
                    hint=f"use jnp.{root.attr}.{fn.attr}")
            elif fn.attr in _HOST_METHODS and _mentions_traced(
                    fn.value, self.jit_ctx):
                self.add(
                    "TX-J01", node,
                    f".{fn.attr}() on a traced value inside jitted "
                    f"{self.jit_fn_name!r} — forces a device->host "
                    f"transfer and a blocking sync",
                    ERROR,
                    hint="keep the value on device; materialize results "
                         "only OUTSIDE the jitted function")
        elif isinstance(fn, ast.Name) and fn.id in ("float", "int", "bool") \
                and node.args and _mentions_traced(node.args[0],
                                                   self.jit_ctx):
            self.add(
                "TX-J01", node,
                f"{fn.id}(...) applied to a traced value inside jitted "
                f"{self.jit_fn_name!r} — concretizes the tracer "
                f"(ConcretizationTypeError at trace time)",
                ERROR,
                hint="use .astype(...) for dtype casts; scalar reads "
                     "belong outside the jitted function")

    # -- module-level jit assignments for TX-J03 ---------------------------
    def visit_Assign(self, node: ast.Assign) -> None:
        if not self.fn_stack and isinstance(node.value, ast.Call) \
                and self.al.is_jax_jit(node.value.func) \
                and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            self.jitted_statics[node.targets[0].id] = \
                _static_names_from_call(node.value, None)
        # TX-J07: per-grid-point taint flows through plain aliasing
        # (`p = grid[i]`, `depth = p["max_depth"]`, `cfg = dict(p)`) but
        # stops at aggregates and non-trivial calls (grouped statics)
        if self.grid_ctx is not None \
                and self._is_grid_alias(node.value):
            for target in node.targets:
                self._taint_targets(target)
        if self.serving:
            for target in node.targets:
                self._check_live_mutation(target)
            self._check_unbounded_queue(node.targets, node.value)
            self._check_writer_store(node.targets, node.value)
        for target in node.targets:
            self._check_tunable_const(target, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        # TX-T01 also covers the annotated form
        # (`DEFAULT_ETA: int = 3`) — same knob, same second source
        self._check_tunable_const(node.target, node.value)
        if self.serving and node.value is not None:
            # TX-R05 covers the annotated spelling too
            # (`self.queue: deque = deque()`)
            self._check_unbounded_queue([node.target], node.value)
        self.generic_visit(node)

    # -- TX-T02 ------------------------------------------------------------
    def _t02(self, node: ast.AST, spelled: str) -> None:
        self.add(
            "TX-T02", node,
            f"hardcoded power-of-two bucket math ({spelled}) outside "
            f"plans/common.py / tuning/lattice.py — a tuned "
            f"non-power-of-two lattice (docs/ragged_batching.md) makes "
            f"this locally re-derived ladder disagree with the plan's "
            f"actual buckets",
            ERROR,
            hint="resolve batch shapes through plans.common.bucket_for/"
                 "pad_rows (lattice-aware) or the tuning.lattice "
                 "helpers instead of local pow2 arithmetic")

    @staticmethod
    def _const_int(node: ast.AST, value: int) -> bool:
        return isinstance(node, ast.Constant) \
            and type(node.value) is int and node.value == value

    def visit_BinOp(self, node: ast.BinOp) -> None:
        # TX-T02: `1 << n` / `2 ** n` with a COMPUTED exponent is a
        # locally re-derived pow2 bucket ladder. A literal exponent
        # (`2 ** 30`, a plain size constant) is just a number — exempt.
        if self.bucket_math_path and not _is_numeric_literal(node.right):
            if isinstance(node.op, ast.LShift) \
                    and self._const_int(node.left, 1):
                self._t02(node, "1 << <computed>")
            elif isinstance(node.op, ast.Pow) \
                    and self._const_int(node.left, 2):
                self._t02(node, "2 ** <computed>")
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        if self.serving:
            self._check_live_mutation(node.target)
        # TX-T02: `b *= 2` / `b <<= 1` doubling loops grow a pow2
        # ladder in place — same forked-ladder hazard as `1 << n`
        if self.bucket_math_path and (
                (isinstance(node.op, ast.Mult)
                 and self._const_int(node.value, 2))
                or (isinstance(node.op, ast.LShift)
                    and self._const_int(node.value, 1))):
            self._t02(node, "<row count> *= 2")
        self.generic_visit(node)

    def visit_Delete(self, node: ast.Delete) -> None:
        if self.serving:
            for target in node.targets:
                self._check_live_mutation(target, deleting=True)
                # TX-R07: `del container[key]` is a cleanup path
                if isinstance(target, ast.Subscript):
                    cname = self._r07_container_name(target.value)
                    if cname is not None:
                        self._writer_cleanups.add(cname)
        self.generic_visit(node)

    def _check_live_mutation(self, target: ast.AST,
                             deleting: bool = False) -> None:
        """TX-R03: a store (or del) that rewrites a live serving cache
        entry or a plan registry in place, outside the owning object's
        own methods. Legal hot changes go through the atomic helpers
        (``PlanCache.swap_entry`` pins the previous entry and replaces
        the reference in ONE assignment; ``rollback``/``commit``
        resolve the pin) so concurrent readers only ever see a
        complete entry."""
        verb = "del of" if deleting else "write to"
        if isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._check_live_mutation(elt, deleting)
            return
        if isinstance(target, ast.Attribute) \
                and target.attr in _R03_ENTRY_ATTRS \
                and not _is_self_name(target.value):
            self.add(
                "TX-R03", target,
                f"in-place {verb} '.{target.attr}' on a live serving "
                f"cache entry — in-flight batches hold a reference to "
                f"this object and there is no pinned previous value "
                f"to roll back to",
                ERROR,
                hint="build a fresh entry and replace it atomically "
                     "with PlanCache.swap_entry(...); rollback()/"
                     "commit() resolve the pinned predecessor")
            return
        if isinstance(target, ast.Subscript) \
                and isinstance(target.value, ast.Attribute) \
                and target.value.attr in _R03_REGISTRY_ATTRS \
                and not _is_self_name(target.value.value):
            self.add(
                "TX-R03", target,
                f"direct {verb} '.{target.value.attr}[...]' on another "
                f"object's plan registry bypasses the swap/rollback "
                f"pin bookkeeping",
                ERROR,
                hint="use PlanCache.swap_entry(name, entry, "
                     "tenant=...) / rollback(...) / commit(...)")


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def _register_module_jits(tree: ast.Module, al: _Aliases,
                          visitor: _Visitor) -> None:
    """Pre-pass: collect every module-level jitted function and its
    static argnames BEFORE the main walk, so call sites earlier in the
    file still get TX-J03 coverage."""
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            statics = _jit_decoration(node, al)
            if statics is not None:
                visitor.jitted_statics[node.name] = statics
            if any(al.is_lru_cache(d) for d in node.decorator_list):
                # memoized kernel builders: their ARGUMENTS key the
                # compile cache, so grid taint reaching them is TX-J07
                visitor.memoized_builders.add(node.name)
        elif isinstance(node, ast.Assign) \
                and isinstance(node.value, ast.Call) \
                and al.is_jax_jit(node.value.func) \
                and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            visitor.jitted_statics[node.targets[0].id] = \
                _static_names_from_call(node.value, None)


def lint_source(source: str, path: str = "<string>") -> List[LintFinding]:
    """Run every JAX AST rule over one source text."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [LintFinding(
            rule_id="TX-E00", severity=ERROR, path=path,
            line=e.lineno or 0,
            message=f"source does not parse: {e.msg}")]
    al = _Aliases.collect(tree)
    visitor = _Visitor(path, al)
    _register_module_jits(tree, al, visitor)
    visitor.visit(tree)
    visitor.finalize()
    return visitor.findings


def lint_file(path: str) -> List[LintFinding]:
    with open(path, encoding="utf-8") as fh:
        return lint_source(fh.read(), path)


def abstract_probe(fn, *arg_specs) -> List[LintFinding]:
    """Confirm compile-path defects by ABSTRACT tracing — ``jax.eval_shape``
    runs the function with shape/dtype-only values: no device buffer is
    allocated, no XLA program compiled, no kernel executed. Defects the
    AST can't see statically (host transfers / concretization behind
    dynamic dispatch) surface as typed exceptions here; float64 results
    surface in the output aval dtypes.

    ``arg_specs`` are ``jax.ShapeDtypeStruct``s (or arrays, used only
    for their avals)."""
    import jax
    import jax.numpy as jnp  # noqa: F401  (fn under probe usually needs it)

    name = getattr(fn, "__name__", repr(fn))
    findings: List[LintFinding] = []
    try:
        out = jax.eval_shape(fn, *arg_specs)
    except jax.errors.TracerArrayConversionError as e:
        findings.append(LintFinding(
            rule_id="TX-J01", severity=ERROR, subject=name,
            message=f"abstract probe of {name!r}: traced value converted "
                    f"to a host numpy array ({type(e).__name__})",
            hint="replace np.* with jnp.* inside the device function"))
        return findings
    except (jax.errors.TracerBoolConversionError,
            jax.errors.ConcretizationTypeError) as e:
        findings.append(LintFinding(
            rule_id="TX-J05", severity=ERROR, subject=name,
            message=f"abstract probe of {name!r}: Python control flow "
                    f"required a concrete traced value "
                    f"({type(e).__name__})",
            hint="use lax.cond / lax.while_loop / jnp.where, or mark the "
                 "argument static"))
        return findings
    import jax.tree_util as jtu
    for leaf in jtu.tree_leaves(out):
        dtype = getattr(leaf, "dtype", None)
        if dtype is not None and str(dtype) == "float64":
            findings.append(LintFinding(
                rule_id="TX-J04", severity=WARNING, subject=name,
                message=f"abstract probe of {name!r}: output has dtype "
                        f"float64",
                hint="cast to float32 before returning; TPUs have no "
                     "native f64 path"))
    return findings
