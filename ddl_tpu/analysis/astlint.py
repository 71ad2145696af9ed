"""AST lint rules over the ddl_tpu package — no JAX import required.

The classes of bug these rules catch share one property: they are
*silent* on a TPU run.  A ``float()`` inside a jitted step either throws
a ConcretizationError at trace time (best case) or forces a host
round-trip per step (worst case — the step graph is cut and MFU halves
with no error anywhere); an unknown mesh axis in a ``PartitionSpec``
replicates the array instead of sharding it; an obs event emitted under
a typo'd name silently never matches any dashboard/CI query.

Engine: per module, build the set of **traced functions** — functions
whose code runs under a JAX trace — then apply host-interop rules only
inside that set (a ``float()`` in the host-side logging path is fine;
the same call inside ``loss_fn`` is a bug).  Traced functions are found
by reference, not by name:

* a function passed to (or decorating with) a JAX transform —
  ``jax.jit`` / ``grad`` / ``value_and_grad`` / ``vmap`` / ``shard_map``
  / ``lax.scan|cond|while_loop|fori_loop`` / ``checkpoint`` /
  ``pallas_call`` — is a traced root;
* **sink parameters** propagate interprocedurally within a module: if
  function ``F`` passes its parameter ``p`` into a transform (or into
  another function's sink parameter, or calls ``p`` from traced code),
  then any local function passed as ``p`` at an ``F`` call site is
  traced — this is how ``loss_fn`` handed through
  ``finalize_step_fns`` → ``jax.value_and_grad`` is found;
* functions lexically nested in a traced function, and functions called
  by name from traced code, are traced (closure to fixpoint).

**Cross-module inference** (``infer_traced_program``): when linting the
whole package, the per-module fixpoint runs inside an outer fixpoint
over the import/call graph (``callgraph.py``) — a function in
``utils/`` called from traced code in ``train/`` (directly, through an
``import`` alias, a re-export, or by being passed into another module's
sink parameter) becomes traced in *its* module, and the host-interop
rules fire there with a ``(traced via …)`` provenance note.  A host
sync hidden behind a helper in a different module is no longer
invisible.  ``lint_file`` on an explicit path stays single-file (fast,
editor-on-save); the sharding-contract checker (``contracts.py``)
still covers composition at trace level.

Beyond the host-interop rules, the module also carries the
**collective-symmetry** family (a ``coord`` barrier/agree/arrive, a
``lax`` collective, or a ``Rendezvous`` method reachable only under a
host-dependent condition — ``host_id``/``process_index``/``DDL_*`` env
— is a split-brain hang: the hosts that don't take the branch never
arrive) and the **recompile-hazard** family (Python branching on traced
``.shape``/``.dtype``, unhashable or freshly-constructed static args at
``jit`` boundaries, traced functions closing over mutable module
globals — the failure class where steps/s craters with no error
anywhere because XLA silently compiles a new program per step).
"""

from __future__ import annotations

import ast
import dataclasses
from pathlib import Path

from ddl_tpu.analysis.findings import Finding, suppressed

__all__ = [
    "Registry",
    "infer_traced_program",
    "lint_file",
    "lint_package",
    "load_registry",
    "MESH_AXES",
]

# The mesh-axis vocabulary (parallel/mesh.py + parallel/sharding.py).
# PartitionSpec literals anywhere in the package must draw from this set
# (or from an axis tuple declared in a same-module Mesh(...) literal).
MESH_AXES = frozenset({"data", "pipe", "seq", "model", "expert"})

# Calls that put their function arguments under a JAX trace.
_TRANSFORMS = frozenset({
    "jax.jit", "jit", "nn.jit",
    "jax.grad", "jax.value_and_grad", "jax.vjp", "jax.jvp", "jax.linearize",
    "jax.vmap", "jax.pmap",
    "jax.shard_map", "shard_map",
    "jax.checkpoint", "jax.remat", "nn.remat", "checkpoint", "remat",
    "jax.eval_shape",
    "jax.lax.scan", "lax.scan", "jax.lax.cond", "lax.cond",
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.map", "lax.map",
    "jax.lax.associative_scan", "lax.associative_scan",
    "pl.pallas_call", "pallas_call",
})

# Host-synchronisation calls: inside traced code these either fail the
# trace or silently cut the compiled program at a host round-trip.
_HOST_SYNC_DOTTED = frozenset({
    "jax.device_get", "device_get",
    "np.asarray", "numpy.asarray", "np.array", "numpy.array",
    "jax.block_until_ready",
})
_HOST_SYNC_METHODS = frozenset({"item", "block_until_ready"})

_NONDET_DOTTED = frozenset({
    "time.time", "time.perf_counter", "time.monotonic", "time.time_ns",
    "datetime.datetime.now", "datetime.datetime.utcnow",
})

# Modules whose exception handling gates checkpoint/recovery decisions:
# an over-broad swallow here turns a real corruption into silent data
# loss, so `except Exception` without a re-raise is flagged.
_RECOVERY_MODULES = frozenset({
    "checkpoint.py",
    "coord.py",
    "supervisor.py",
    "train/recovery.py",
    "train/loop.py",
    "utils/preemption.py",
    "utils/backoff.py",
    "utils/faultinject.py",
    "obs/watchdog.py",
    "obs/steptrace.py",
})

# Step-function factory modules: every jitted train step must declare
# buffer donation (checked here) — whether the compiled step aliases
# the donated buffers is the contract checker's concern
# (contracts.py zero_donation).
_STEP_MODULES = frozenset({
    "train/steps.py",
    "train/lm_steps.py",
    "train/vit_steps.py",
    "parallel/lm_pipeline.py",
})

# Step-factory modules where parameter/batch placement must come from
# the partition-rule engine (parallel/rules.py): a hand-written
# PartitionSpec axis literal here bypasses the rule tables the contract
# probes validate — the exact drift the engine exists to prevent.
# Derived specs (P(), P(None, *TOKEN_SPEC), axis *variables*) are fine;
# only hard-coded axis name strings are flagged.
_RULE_ENGINE_MODULES = frozenset({
    "train/steps.py",
    "train/lm_steps.py",
    "train/vit_steps.py",
})

# Pod-coordination paths: a process that hard-exits here without first
# publishing exit intent through the rendezvous strands its peers inside
# a dead collective until heartbeat ageout — the exact hang the coord
# layer exists to prevent.  Any os._exit/sys.exit use (call OR the
# function object handed around as an escape hatch) inside a function
# that never publishes intent is flagged.
_COORD_EXIT_MODULES = frozenset({
    "supervisor.py",
    "coord.py",
    "obs/watchdog.py",
})

# Collective-symmetry scope: the modules where a host-conditionally-
# reachable collective/barrier is a pod-hang, not a style nit.  The
# coordination layer itself, the shared training loop, and the step
# factories (whose traced collectives must be identical on every host
# of the SPMD world).
_COLLECTIVE_MODULES = frozenset({
    "coord.py",
    "supervisor.py",
    "train/loop.py",
}) | _STEP_MODULES

# lax collectives: every host of the mesh must execute the same sequence
# or the program hangs (PAPERS.md "Collective Communication for 100k+
# GPUs" — asymmetric collectives are the dominant at-scale hang class).
_COLLECTIVE_LAST = frozenset({
    "psum", "pmean", "pmax", "pmin", "psum_scatter",
    "all_gather", "all_to_all", "ppermute", "pshuffle",
})
_COLLECTIVE_PREFIXES = ("", "lax", "jax.lax")

# Blocking Rendezvous primitives (coord.py): `barrier` and `agree` wait
# for peers, and a host-conditional `arrive` starves every peer's
# blocking wait on that barrier — all three must be symmetric.
_BARRIER_ATTRS = frozenset({"barrier", "agree", "arrive"})

# Names whose appearance in a branch condition makes the branch
# host-dependent: different hosts of one pod evaluate it differently.
_HOST_COND_NAMES = frozenset({
    "host", "host_id", "rank", "process_index", "process_id",
})

# Constructor calls that are safe as jit static args: value-hashed
# built-ins (a fresh `tuple(...)` of equal elements cache-hits; a fresh
# instance of an arbitrary class identity-hashes and never does).
_VALUE_HASHED_CTORS = frozenset({
    "tuple", "frozenset", "int", "float", "bool", "str", "bytes", "len",
})

# Call forms that build a mutable container (module-global hazard).
_MUTABLE_CTORS = frozenset({
    "dict", "list", "set", "bytearray",
    "defaultdict", "collections.defaultdict",
    "deque", "collections.deque",
    "Counter", "collections.Counter",
    "OrderedDict", "collections.OrderedDict",
})
_MUTABLE_LITERALS = (
    ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp,
)


@dataclasses.dataclass
class Registry:
    """Names the obs-event rules validate against, parsed from
    ``<package>/obs/events.py`` without importing it.  ``kind_lines``
    maps each EVENT_KINDS entry to its source line (where the
    dead-event-kind rule anchors its finding and reads suppressions)."""

    event_kinds: frozenset[str]
    anomaly_types: frozenset[str]
    kind_lines: dict[str, int] = dataclasses.field(default_factory=dict)


def load_registry(package_root: Path) -> Registry:
    """Parse EVENT_KINDS / ANOMALY_TYPES tuples out of obs/events.py.
    A package without one (fixture packages) gets an empty registry —
    the obs rules simply have nothing to check against."""
    try:
        src = (Path(package_root) / "obs" / "events.py").read_text()
    except OSError:
        return Registry(frozenset(), frozenset())
    tree = ast.parse(src)
    found: dict[str, frozenset] = {}
    kind_lines: dict[str, int] = {}
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        if not isinstance(target, ast.Name):
            continue
        if target.id in ("EVENT_KINDS", "ANOMALY_TYPES"):
            consts = [
                e
                for e in ast.walk(node.value)
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            ]
            found[target.id] = frozenset(e.value for e in consts)
            if target.id == "EVENT_KINDS":
                kind_lines = {e.value: e.lineno for e in consts}
    return Registry(
        event_kinds=found.get("EVENT_KINDS", frozenset()),
        anomaly_types=found.get("ANOMALY_TYPES", frozenset()),
        kind_lines=kind_lines,
    )


# ---------------------------------------------------------------------------
# module model: functions, imports, traced-set inference
# ---------------------------------------------------------------------------


def _dotted(node: ast.AST) -> str | None:
    """'jax.lax.scan' for nested Attribute/Name chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


@dataclasses.dataclass
class _Func:
    node: ast.AST
    name: str
    parent: "_Func | None"
    params: tuple[str, ...]
    sink_params: set[str] = dataclasses.field(default_factory=set)


class _Module:
    """One parsed module with enough structure for the traced-set
    inference: functions (with lexical nesting), every call site (with
    its innermost enclosing function), the import alias map, and —
    since the class-method round — classes: each class's direct
    methods, its base-name list, every function's enclosing class
    context (what ``self.m()`` resolves against), and a conservative
    ``var = ClassName(...)`` instance map (what ``obj.m()`` resolves
    against)."""

    def __init__(self, tree: ast.Module) -> None:
        self.funcs: dict[int, _Func] = {}
        self.by_name: dict[str, list[_Func]] = {}
        self.calls: list[tuple[ast.Call, _Func | None]] = []
        self.imports: dict[str, str] = {}  # local alias -> real module
        # class name -> {direct method name -> _Func}
        self.classes: dict[str, dict[str, _Func]] = {}
        # class name -> dotted base names (single-expression bases only)
        self.class_bases: dict[str, list[str]] = {}
        # id(func node) -> name of the class whose body (transitively)
        # contains it — the receiver type of ``self``/``cls`` there
        self.cls_context: dict[int, str] = {}
        # (id(enclosing func node) | None, var) -> constructor dotted
        # name, from simple ``var = C(...)`` assignments (last wins)
        self.var_classes: dict[tuple, str] = {}
        self._index(tree)

    def _index(self, tree: ast.Module) -> None:
        stack: list[_Func] = []
        class_stack: list[tuple[str, int]] = []  # (name, func depth)

        def visit(node: ast.AST) -> None:
            if isinstance(node, _FUNC_NODES):
                name = getattr(node, "name", "<lambda>")
                args = node.args
                params = tuple(
                    a.arg
                    for a in (
                        args.posonlyargs + args.args + args.kwonlyargs
                    )
                )
                fn = _Func(node, name, stack[-1] if stack else None, params)
                self.funcs[id(node)] = fn
                self.by_name.setdefault(name, []).append(fn)
                if class_stack:
                    cname, depth = class_stack[-1]
                    self.cls_context[id(node)] = cname
                    if len(stack) == depth:  # directly in the class body
                        self.classes.setdefault(cname, {})[name] = fn
                stack.append(fn)
                for child in ast.iter_child_nodes(node):
                    visit(child)
                stack.pop()
                return
            if isinstance(node, ast.ClassDef):
                self.classes.setdefault(node.name, {})
                self.class_bases[node.name] = [
                    b for b in (_dotted(base) for base in node.bases)
                    if b is not None
                ]
                class_stack.append((node.name, len(stack)))
                for child in ast.iter_child_nodes(node):
                    visit(child)
                class_stack.pop()
                return
            if isinstance(node, ast.Call):
                self.calls.append((node, stack[-1] if stack else None))
            elif isinstance(node, ast.Assign):
                # conservative instance typing: ``var = C(...)`` with a
                # single Name target; re-assignment rebinds (last wins)
                if (
                    len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)
                ):
                    ctor = _dotted(node.value.func)
                    if ctor is not None:
                        scope = id(stack[-1].node) if stack else None
                        self.var_classes[
                            (scope, node.targets[0].id)
                        ] = ctor
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    self.imports[alias.asname or alias.name.split(".")[0]] = (
                        alias.name
                    )
            elif isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    self.imports[alias.asname or alias.name] = (
                        f"{node.module}.{alias.name}" if node.module
                        else alias.name
                    )
            for child in ast.iter_child_nodes(node):
                visit(child)

        visit(tree)

    # -- resolution helpers -------------------------------------------------

    def resolve_func(
        self, expr: ast.AST, enclosing: "_Func | None" = None
    ) -> _Func | None:
        """A Name (or functools.partial(Name, ...)) referring to a
        module function, else None.  With ``enclosing`` (the call
        site's innermost function) resolution is scope-aware: among
        same-named definitions, the one defined in the NEAREST lexical
        scope of the call site wins — so three factories each defining
        a local ``step`` resolve their own, not whichever parsed last."""
        if isinstance(expr, ast.Call) and _is_partial(expr.func):
            return (
                self.resolve_func(expr.args[0], enclosing)
                if expr.args else None
            )
        if not isinstance(expr, ast.Name):
            return None
        candidates = self.by_name.get(expr.id)
        if not candidates:
            return None
        if enclosing is not None:
            chain_ids = [
                id(f.node) for f in self.enclosing_chain(enclosing)
            ]  # innermost -> outermost
            best, best_rank = None, None
            for c in candidates:
                if c.parent is None:
                    rank = len(chain_ids)  # module scope: outermost
                elif id(c.parent.node) in chain_ids:
                    rank = chain_ids.index(id(c.parent.node))
                else:
                    continue  # not lexically visible from the call site
                # <=: a later definition at the same depth rebinds
                if best_rank is None or rank <= best_rank:
                    best, best_rank = c, rank
            if best is not None:
                return best
        top = [c for c in candidates if c.parent is None]
        return (top or candidates)[-1]

    def enclosing_chain(self, fn: _Func | None):
        while fn is not None:
            yield fn
            fn = fn.parent

    # -- class-method resolution --------------------------------------------

    def lookup_method(
        self, cls_name: str, meth: str, _depth: int = 0
    ) -> "_Func | None":
        """``cls_name``'s method ``meth``, chasing same-module base
        classes to a bounded depth (cross-module bases resolve at the
        call-graph layer)."""
        if _depth > 8:
            return None
        methods = self.classes.get(cls_name)
        if methods is None:
            return None
        if meth in methods:
            return methods[meth]
        for base in self.class_bases.get(cls_name, ()):
            found = self.lookup_method(base, meth, _depth + 1)
            if found is not None:
                return found
        return None

    def instance_class(
        self, name: str, enclosing: "_Func | None"
    ) -> str | None:
        """The constructor dotted name a variable was bound to
        (``obj = C(...)``), nearest enclosing scope first, module scope
        last — or None when the variable's type is not statically
        evident."""
        for outer in self.enclosing_chain(enclosing):
            ctor = self.var_classes.get((id(outer.node), name))
            if ctor is not None:
                return ctor
        return self.var_classes.get((None, name))

    def resolve_method(
        self, expr: ast.AST, enclosing: "_Func | None"
    ) -> "_Func | None":
        """A method call/reference resolved WITHIN this module:
        ``self.m()`` / ``cls.m()`` against the call site's enclosing
        class, ``C.m`` against a local class, ``obj.m()`` against a
        local ``obj = C(...)`` binding.  Cross-module receivers return
        None here and are chased by ``_resolve_callable`` through the
        call graph."""
        if not isinstance(expr, ast.Attribute) or not isinstance(
            expr.value, (ast.Name, ast.Attribute)
        ):
            return None
        meth = expr.attr
        base = _dotted(expr.value)
        if base is None:
            return None
        if base in ("self", "cls"):
            for outer in self.enclosing_chain(enclosing):
                cname = self.cls_context.get(id(outer.node))
                if cname is not None:
                    return self.lookup_method(cname, meth)
            return None
        if base in self.classes:  # C.m (unbound reference)
            return self.lookup_method(base, meth)
        ctor = self.instance_class(base, enclosing)
        if ctor is not None and ctor in self.classes:
            return self.lookup_method(ctor, meth)
        return None


def _is_partial(func_expr: ast.AST) -> bool:
    d = _dotted(func_expr)
    return d in ("partial", "functools.partial")


def _is_transform(call: ast.Call) -> bool:
    d = _dotted(call.func)
    if d in _TRANSFORMS:
        return True
    # partial(jax.jit, ...) / partial(lax.scan, ...) as the callee
    if _is_partial(call.func):
        return False  # handled at the inner-arg level by callers
    return False


def _func_args(call: ast.Call):
    """Every expression passed to a call (positional + keyword)."""
    yield from call.args
    for kw in call.keywords:
        if kw.value is not None:
            yield kw.value


def _resolve_local(mod: _Module, expr: ast.AST, enclosing) -> _Func | None:
    """Local callable resolution: module functions (scope-aware) first,
    then class methods (``self.m`` / ``C.m`` / ``obj.m`` with a local
    ``obj = C(...)`` binding); partial-wrapped references unwrap."""
    if isinstance(expr, ast.Call) and _is_partial(expr.func):
        return (
            _resolve_local(mod, expr.args[0], enclosing)
            if expr.args else None
        )
    fn = mod.resolve_func(expr, enclosing)
    if fn is not None:
        return fn
    return mod.resolve_method(expr, enclosing)


def _infer_traced(
    mod: _Module, traced: set[int] | None = None
) -> set[int]:
    """Fixpoint over {traced functions} x {sink parameters}.  An
    existing ``traced`` set (cross-module seeds from
    ``infer_traced_program``) is extended in place."""
    traced = set() if traced is None else traced

    # seeds: decorators that are transforms
    for fn in mod.funcs.values():
        for dec in getattr(fn.node, "decorator_list", []):
            target = dec.func if isinstance(dec, ast.Call) else dec
            d = _dotted(target)
            if d in _TRANSFORMS:
                traced.add(id(fn.node))
            elif isinstance(dec, ast.Call) and _is_partial(dec.func):
                if dec.args and _dotted(dec.args[0]) in _TRANSFORMS:
                    traced.add(id(fn.node))

    changed = True
    while changed:
        changed = False

        for call, enclosing in mod.calls:
            callee_d = _dotted(call.func)

            # (1) function reference passed into a transform -> traced root
            transform_call = callee_d in _TRANSFORMS or (
                _is_partial(call.func)
                and call.args
                and _dotted(call.args[0]) in _TRANSFORMS
            )
            if transform_call:
                for arg in _func_args(call):
                    target = _resolve_local(mod, arg, enclosing)
                    if target is not None and id(target.node) not in traced:
                        traced.add(id(target.node))
                        changed = True
                # a parameter of an enclosing function fed to a transform
                # makes that parameter a sink
                for arg in _func_args(call):
                    base = arg
                    if isinstance(arg, ast.Call) and _is_partial(arg.func):
                        base = arg.args[0] if arg.args else arg
                    if isinstance(base, ast.Name) and enclosing is not None:
                        for outer in mod.enclosing_chain(enclosing):
                            if base.id in outer.params and (
                                base.id not in outer.sink_params
                            ):
                                outer.sink_params.add(base.id)
                                changed = True

            # (2) call to a local function with sink params: map args
            callee_fn = _resolve_local(mod, call.func, enclosing)
            if callee_fn is not None and callee_fn.sink_params:
                bound: list[tuple[str, ast.AST]] = []
                for i, arg in enumerate(call.args):
                    if i < len(callee_fn.params):
                        bound.append((callee_fn.params[i], arg))
                for kw in call.keywords:
                    if kw.arg is not None:
                        bound.append((kw.arg, kw.value))
                for pname, arg in bound:
                    if pname not in callee_fn.sink_params:
                        continue
                    target = _resolve_local(mod, arg, enclosing)
                    if target is not None and id(target.node) not in traced:
                        traced.add(id(target.node))
                        changed = True
                    base = arg
                    if isinstance(arg, ast.Call) and _is_partial(arg.func):
                        base = arg.args[0] if arg.args else arg
                    if isinstance(base, ast.Name) and enclosing is not None:
                        for outer in mod.enclosing_chain(enclosing):
                            if base.id in outer.params and (
                                base.id not in outer.sink_params
                            ):
                                outer.sink_params.add(base.id)
                                changed = True

            # (3) inside a traced function: called names become traced,
            # and a *called parameter* of an enclosing function is a sink
            # (accumulate_grads' scan body calling grad_fn)
            if enclosing is not None and id(enclosing.node) in traced:
                target = _resolve_local(mod, call.func, enclosing)
                if target is not None and id(target.node) not in traced:
                    traced.add(id(target.node))
                    changed = True
                if isinstance(call.func, ast.Name):
                    for outer in mod.enclosing_chain(enclosing):
                        if call.func.id in outer.params and (
                            call.func.id not in outer.sink_params
                        ):
                            outer.sink_params.add(call.func.id)
                            changed = True

        # (4) lexical nesting: children of traced functions are traced
        for fn in mod.funcs.values():
            if id(fn.node) in traced:
                continue
            if fn.parent is not None and id(fn.parent.node) in traced:
                traced.add(id(fn.node))
                changed = True

    return traced


# ---------------------------------------------------------------------------
# cross-module traced-set inference (over callgraph.CallGraph)
# ---------------------------------------------------------------------------


def _resolve_callable(graph, info, expr, enclosing=None):
    """A Target for a callee/argument expression: a Name or dotted
    Attribute chain (optionally wrapped in functools.partial).  Local
    scope-aware resolution first (the call site's own module binds
    tightest), then the cross-module import/re-export chase."""
    if isinstance(expr, ast.Call) and _is_partial(expr.func):
        return (
            _resolve_callable(graph, info, expr.args[0], enclosing)
            if expr.args else None
        )
    if isinstance(expr, ast.Name):
        local = info.mod.resolve_func(expr, enclosing)
        if local is not None:
            from ddl_tpu.analysis.callgraph import Target

            return Target(info.name, local)
    d = _dotted(expr)
    if d is not None:
        t = graph.resolve_dotted(info, d)
        if t is not None:
            return t
    # class-method edges: self.m()/C.m/obj.m() resolved locally first,
    # then an imported receiver class chased through the call graph
    if isinstance(expr, ast.Attribute):
        local_m = info.mod.resolve_method(expr, enclosing)
        if local_m is not None:
            from ddl_tpu.analysis.callgraph import Target

            return Target(info.name, local_m)
        if isinstance(expr.value, ast.Name):
            ctor = info.mod.instance_class(expr.value.id, enclosing)
            if ctor is not None:
                return graph.resolve_class_method(info, ctor, expr.attr)
    return None


def infer_traced_program(graph):
    """Traced sets for every module of a ``callgraph.CallGraph``,
    propagated interprocedurally ACROSS module boundaries.

    Returns ``(traced, reasons)`` where ``traced`` maps module name to
    the set of traced function-node ids and ``reasons`` maps
    ``(module, node_id)`` to a human-readable provenance string for
    functions traced only through a cross-module edge (so a finding in
    ``utils/helpers.py`` can say which step factory pulled it under a
    trace).

    The outer fixpoint interleaves three cross-module edges with the
    per-module closure (``_infer_traced``):

    * a function *reference* resolved into another module passed to a
      JAX transform (``jax.jit(helpers.step)``) → traced root there;
    * a *call* from traced code resolved into another module
      (``helpers.sync_mean(loss)`` inside ``loss_fn``) → callee traced;
    * an argument bound to another module's **sink parameter**
      (``wrap_loss(inner)`` where ``wrap_loss`` in another module feeds
      its parameter into ``value_and_grad``) → the argument is traced,
      and a parameter of the *calling* function forwarded that way
      becomes a sink itself.
    """
    traced: dict[str, set[int]] = {}
    reasons: dict[tuple[str, int], str] = {}
    for name, info in graph.modules.items():
        traced[name] = _infer_traced(info.mod)

    def mark(target, why: str) -> bool:
        s = traced[target.module]
        if id(target.func.node) in s:
            return False
        s.add(id(target.func.node))
        reasons.setdefault((target.module, id(target.func.node)), why)
        return True

    def size() -> int:
        return sum(len(s) for s in traced.values()) + sum(
            len(fn.sink_params)
            for info in graph.modules.values()
            for fn in info.mod.funcs.values()
        )

    while True:
        before = size()
        for name, info in graph.modules.items():
            tset = traced[name]
            for call, enclosing in info.mod.calls:
                callee_d = _dotted(call.func)
                transform_call = callee_d in _TRANSFORMS or (
                    _is_partial(call.func)
                    and call.args
                    and _dotted(call.args[0]) in _TRANSFORMS
                )
                if transform_call:
                    for arg in _func_args(call):
                        t = _resolve_callable(graph, info, arg, enclosing)
                        if t is not None and t.module != name:
                            mark(t, f"passed to a JAX transform in {info.rel}")
                    continue
                callee = _resolve_callable(graph, info, call.func, enclosing)
                # call FROM traced code into another module
                if (
                    enclosing is not None
                    and id(enclosing.node) in tset
                    and callee is not None
                    and callee.module != name
                ):
                    mark(
                        callee,
                        f"called from traced code in "
                        f"{info.rel}::{enclosing.name}",
                    )
                # arguments bound to a cross-module callee's sink params
                if callee is not None and callee.func.sink_params:
                    bound: list[tuple[str, ast.AST]] = []
                    for i, arg in enumerate(call.args):
                        if i < len(callee.func.params):
                            bound.append((callee.func.params[i], arg))
                    for kw in call.keywords:
                        if kw.arg is not None:
                            bound.append((kw.arg, kw.value))
                    for pname, arg in bound:
                        if pname not in callee.func.sink_params:
                            continue
                        t = _resolve_callable(graph, info, arg, enclosing)
                        if t is not None:
                            mark(
                                t,
                                f"flows into traced sink parameter "
                                f"{pname!r} of {callee.module}."
                                f"{callee.func.name}",
                            )
                        # forwarding an own parameter into a foreign sink
                        # makes it a sink here too
                        base = arg
                        if isinstance(arg, ast.Call) and _is_partial(arg.func):
                            base = arg.args[0] if arg.args else arg
                        if isinstance(base, ast.Name) and enclosing is not None:
                            for outer in info.mod.enclosing_chain(enclosing):
                                if base.id in outer.params:
                                    outer.sink_params.add(base.id)
            # close locally with the augmented set (lexical children and
            # same-module calls of newly-traced functions)
            _infer_traced(info.mod, traced=tset)
        if size() == before:
            return traced, reasons


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


def _iter_with_enclosing(tree: ast.Module, mod: _Module):
    """(node, innermost enclosing _Func or None) for every node."""
    stack: list[_Func] = []

    def visit(node: ast.AST):
        entered = False
        if isinstance(node, _FUNC_NODES):
            stack.append(mod.funcs[id(node)])
            entered = True
        yield node, (stack[-1] if stack else None)
        for child in ast.iter_child_nodes(node):
            yield from visit(child)
        if entered:
            stack.pop()

    # yield with the *enclosing* function, so a FunctionDef node itself
    # reports under its own scope (fine for our rules)
    yield from visit(tree)


def _rule_traced_interop(
    tree, mod: _Module, traced: set[int], rel: str, add,
    reasons: dict[int, str] | None = None,
) -> None:
    def via(enclosing) -> str:
        # provenance for functions traced only through a cross-module
        # edge: names the step factory (etc.) that pulled them under a
        # trace, so a finding in utils/ is actionable without grepping
        why = (reasons or {}).get(id(enclosing.node))
        return f" (traced: {why})" if why else ""

    for node, enclosing in _iter_with_enclosing(tree, mod):
        if enclosing is None or id(enclosing.node) not in traced:
            continue
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            full = None
            if d is not None:
                first, *rest = d.split(".")
                full = ".".join([mod.imports.get(first, first)] + rest)
            if d in _HOST_SYNC_DOTTED or full in _HOST_SYNC_DOTTED:
                add(node, "host-sync",
                    f"{d}() inside traced function "
                    f"'{enclosing.name}' forces a host sync (or fails the "
                    "trace); keep device values on device until the period "
                    f"fence{via(enclosing)}")
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _HOST_SYNC_METHODS
                and not node.args
            ):
                add(node, "host-sync",
                    f".{node.func.attr}() inside traced function "
                    f"'{enclosing.name}' forces a host sync per call{via(enclosing)}")
            elif isinstance(node.func, ast.Name) and node.func.id == "float":
                add(node, "host-sync",
                    f"float() inside traced function '{enclosing.name}' "
                    "concretizes a tracer (host sync / trace error); use "
                    f"jnp.float32 or .astype for dtype casts{via(enclosing)}")
            elif full is not None:
                if d in _NONDET_DOTTED or full in _NONDET_DOTTED:
                    add(node, "nondeterminism",
                        f"{d}() inside traced function '{enclosing.name}': "
                        "wall-clock reads bake a constant into the compiled "
                        f"program (and differ across hosts){via(enclosing)}")
                elif full.startswith(("random.", "numpy.random.")):
                    add(node, "nondeterminism",
                        f"{d}() inside traced function '{enclosing.name}': "
                        "Python/NumPy RNG is host-side and per-process; use "
                        f"jax.random with an explicit key{via(enclosing)}")
        elif isinstance(node, (ast.For, ast.comprehension)):
            it = node.iter
            is_set = isinstance(it, ast.Set) or (
                isinstance(it, ast.Call)
                and _dotted(it.func) in ("set", "frozenset")
            )
            if is_set:
                add(node if isinstance(node, ast.For) else it,
                    "nondeterminism",
                    f"iteration over a set inside traced function "
                    f"'{enclosing.name}': set order varies per process, so "
                    "traced program structure diverges across hosts; sort "
                    f"or use a tuple{via(enclosing)}")


def _rule_excepts(tree, rel: str, add) -> None:
    in_recovery = rel_suffix(rel) in _RECOVERY_MODULES
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            add(node, "bare-except",
                "bare 'except:' swallows KeyboardInterrupt/SystemExit too; "
                "name the exceptions (or 'except Exception' plus a re-raise)")
            continue
        if not in_recovery:
            continue
        names = []
        exprs = (
            node.type.elts if isinstance(node.type, ast.Tuple)
            else [node.type]
        )
        for e in exprs:
            d = _dotted(e)
            if d is not None:
                names.append(d.split(".")[-1])
        if any(n in ("Exception", "BaseException") for n in names):
            has_raise = any(
                isinstance(n, ast.Raise) for n in ast.walk(node)
            )
            if not has_raise:
                add(node, "broad-except",
                    f"'except {'/'.join(names)}' without re-raise in a "
                    "checkpoint/recovery path can mask corruption as "
                    "success; narrow the exception list or re-raise")


def _rule_compat(tree, rel: str, add) -> None:
    """Legacy JAX spellings: one installation (jax 0.9.0) is supported
    and nothing aliases the old names any more, so they fail at import
    or call time on the chip — catch them here."""
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            m = node.module or ""
            if m.startswith("jax.experimental.shard_map") or (
                m == "jax.experimental"
                and any(a.name in ("shard_map", "pjit") for a in node.names)
            ):
                add(node, "compat-bypass",
                    "legacy jax.experimental.shard_map/pjit import; use "
                    "jax.shard_map / jax.jit")
            elif m.startswith("jax.experimental.pjit"):
                add(node, "compat-bypass",
                    "legacy pjit import; use jax.jit")
        elif isinstance(node, ast.Attribute):
            d = _dotted(node)
            if d and (
                d.startswith("jax.experimental.shard_map")
                or d.startswith("jax.experimental.pjit")
            ):
                add(node, "compat-bypass",
                    f"direct {d} use is the legacy spelling; use the "
                    "modern jax.* name")
            elif node.attr == "TPUCompilerParams":
                add(node, "compat-bypass",
                    "TPUCompilerParams is the legacy spelling; use "
                    "pltpu.CompilerParams")
        elif isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg == "check_rep":
                    add(node, "compat-bypass",
                        "check_rep= is the legacy shard_map kwarg; pass "
                        "check_vma=")


# Call attrs treated as obs-event emission sites: the writer itself and
# the thin `_emit` forwarders (Supervisor/PodSupervisor wrap EventWriter
# behind one) — their literal kinds must be registered too, and they
# count as "emitted" for the dead-kind rule.
_EMIT_ATTRS = frozenset({"emit", "_emit"})


def _emit_kind_literal(node: ast.Call) -> str | None:
    """The literal event kind an emit/_emit call names, else None."""
    kind = None
    if node.args and isinstance(node.args[0], ast.Constant):
        kind = node.args[0].value
    for kw in node.keywords:
        if kw.arg == "kind" and isinstance(kw.value, ast.Constant):
            kind = kw.value.value
    return kind if isinstance(kind, str) else None


def _rule_obs_events(tree, registry: Registry, rel: str, add) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not isinstance(
            node.func, ast.Attribute
        ):
            continue
        if node.func.attr in _EMIT_ATTRS:
            kind = _emit_kind_literal(node)
            if kind is not None and kind not in registry.event_kinds:
                add(node, "obs-event-unregistered",
                    f"obs event kind {kind!r} is not in "
                    "obs/events.py EVENT_KINDS; register it (or fix the "
                    "typo) so dashboards and CI queries can rely on the "
                    "name")
        elif node.func.attr == "record":
            base = _dotted(node.func.value)
            if base is None or not base.split(".")[-1] == "anomaly":
                continue
            if len(node.args) >= 2 and isinstance(node.args[1], ast.Constant):
                t = node.args[1].value
                if isinstance(t, str) and t not in registry.anomaly_types:
                    add(node, "anomaly-type-unregistered",
                        f"anomaly type {t!r} is not in obs/events.py "
                        "ANOMALY_TYPES; register it so the obs summary and "
                        "alert queries see it")


def _pspec_names(tree, mod: _Module) -> set[str]:
    """Local aliases bound to jax.sharding.PartitionSpec."""
    names = set()
    for alias, real in mod.imports.items():
        if real.endswith("PartitionSpec"):
            names.add(alias)
    names.update({"PartitionSpec"})
    return names


def _rule_pspec(tree, mod: _Module, rel: str, add) -> None:
    pnames = _pspec_names(tree, mod)
    # axis names declared by a same-module Mesh((...), ("ring",)) literal
    # extend the allowed set (bench/comm.py builds its own ring mesh)
    extra: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _dotted(node.func) in (
            "Mesh", "jax.sharding.Mesh"
        ):
            for arg in list(node.args[1:]) + [
                kw.value for kw in node.keywords if kw.arg == "axis_names"
            ]:
                for e in ast.walk(arg):
                    if isinstance(e, ast.Constant) and isinstance(
                        e.value, str
                    ):
                        extra.add(e.value)
    allowed = MESH_AXES | extra
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d not in pnames and d != "jax.sharding.PartitionSpec":
            continue
        for arg in node.args:
            consts = (
                [arg] if isinstance(arg, ast.Constant)
                else list(ast.walk(arg)) if isinstance(arg, ast.Tuple)
                else []
            )
            for e in consts:
                if (
                    isinstance(e, ast.Constant)
                    and isinstance(e.value, str)
                    and e.value not in allowed
                ):
                    add(node, "pspec-unknown-axis",
                        f"PartitionSpec axis {e.value!r} is not a mesh axis "
                        f"({'/'.join(sorted(allowed))}); XLA would treat "
                        "the dimension as replicated — a silent memory/"
                        "throughput loss, never an error")


def _rule_pspec_hand_rolled(tree, mod: _Module, rel: str, add) -> None:
    """In the step-factory modules, flag ``PartitionSpec`` calls that
    hard-code axis-name strings: placement belongs to the family rule
    tables (``parallel/rules.py``), and a literal here silently bypasses
    the table the contract probes validate."""
    if rel_suffix(rel) not in _RULE_ENGINE_MODULES:
        return
    pnames = _pspec_names(tree, mod)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d not in pnames and d != "jax.sharding.PartitionSpec":
            continue
        literals = []
        for arg in node.args:
            consts = (
                [arg] if isinstance(arg, ast.Constant)
                else list(ast.walk(arg)) if isinstance(arg, ast.Tuple)
                else []
            )
            literals.extend(
                e.value for e in consts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            )
        if literals:
            add(node, "pspec-hand-rolled",
                f"hand-written PartitionSpec axis literal(s) "
                f"{sorted(set(literals))} in a step-factory module bypass "
                "the partition-rule engine; use the family rule table / "
                "named boundary specs from parallel/rules.py (derive "
                "variants like P(None, *TOKEN_SPEC))")


def _rule_donation(tree, mod: _Module, rel: str, add) -> None:
    if rel_suffix(rel) not in _STEP_MODULES:
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or _dotted(node.func) not in (
            "jax.jit", "jit"
        ):
            continue
        if not node.args or not isinstance(node.args[0], ast.Name):
            continue
        if "train" not in node.args[0].id:
            continue
        kwargs = {kw.arg for kw in node.keywords}
        if not kwargs & {"donate_argnums", "donate_argnames"}:
            add(node, "donation-missing",
                f"jax.jit({node.args[0].id}, ...) without donate_argnums: "
                "the train state is copied instead of donated — 2x state "
                "HBM held across the update")


def _rule_exit_intent(tree, mod: _Module, rel: str, add) -> None:
    """In coord/supervisor/watchdog paths, an ``os._exit``/``sys.exit``
    whose enclosing function never publishes exit intent bypasses the
    pod protocol (the dying host's peers wait for its heartbeat to age
    out instead of reacting to the marker).  'Publishes intent' is
    lexical: some call in the same function whose name mentions
    ``intent`` (``coord.publish_exit_intent_from_env``,
    ``rv.publish_intent``)."""
    if rel_suffix(rel) not in _COORD_EXIT_MODULES:
        return
    intent_scopes: set[int | None] = set()
    exit_uses: list[tuple[ast.AST, _Func | None, str]] = []
    call_funcs: set[int] = set()  # Attribute nodes already seen as callees

    def scope_key(enclosing: _Func | None):
        return id(enclosing.node) if enclosing is not None else None

    for node, enclosing in _iter_with_enclosing(tree, mod):
        if isinstance(node, ast.Call):
            call_funcs.add(id(node.func))
            d = _dotted(node.func) or ""
            if "intent" in d.lower():
                intent_scopes.add(scope_key(enclosing))
            if d in ("os._exit", "sys.exit"):
                exit_uses.append((node, enclosing, f"{d}()"))
        elif isinstance(node, ast.Attribute) and id(node) not in call_funcs:
            d = _dotted(node)
            if d in ("os._exit", "sys.exit"):
                exit_uses.append((node, enclosing, d))
    for node, enclosing, what in exit_uses:
        if scope_key(enclosing) not in intent_scopes:
            add(node, "exit-without-intent",
                f"{what} in a coord/supervisor path without publishing "
                "exit intent first: peer hosts block inside the dead "
                "collective until heartbeat ageout; call "
                "coord.publish_exit_intent_from_env (or "
                "Rendezvous.publish_intent) before exiting")


# ---------------------------------------------------------------------------
# collective-symmetry rule family
# ---------------------------------------------------------------------------


def _host_dependent_why(test: ast.AST) -> str | None:
    """A short description of why a branch condition is host-dependent
    (different hosts of one pod evaluate it differently), or None."""
    for n in ast.walk(test):
        if isinstance(n, ast.Name) and n.id in _HOST_COND_NAMES:
            return f"reads '{n.id}'"
        if isinstance(n, ast.Attribute) and n.attr in _HOST_COND_NAMES:
            d = _dotted(n)
            return f"reads '{d or n.attr}'"
        if (
            isinstance(n, ast.Constant)
            and isinstance(n.value, str)
            and n.value.startswith("DDL_")
        ):
            return f"branches on env {n.value!r}"
    return None


def _collective_callee(call: ast.Call) -> str | None:
    """'lax.psum' / 'rv.barrier' when the call is a collective or a
    blocking rendezvous primitive, else None."""
    d = _dotted(call.func)
    if d is not None:
        parts = d.split(".")
        if parts[-1] in _COLLECTIVE_LAST and (
            ".".join(parts[:-1]) in _COLLECTIVE_PREFIXES
        ):
            return d
    if (
        isinstance(call.func, ast.Attribute)
        and call.func.attr in _BARRIER_ATTRS
    ):
        return d or f".{call.func.attr}"
    return None


def _suite_terminates(stmts: list) -> bool:
    """True when a statement suite always leaves the enclosing scope /
    loop iteration (its last statement is a return/raise/continue/
    break) — the early-exit shape the asymmetry extension keys on."""
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break)
    )


def _rule_collective_symmetry(tree, mod: _Module, rel: str, add) -> None:
    """In the coordination layer, the shared loop, and the step modules,
    a collective / barrier / agree call reachable only under a
    host-dependent condition is a split-brain hang: the hosts that don't
    take the branch never make the matching call, and the ones that do
    block forever (barrier timeout at best, a wedged all-reduce at
    worst).  Conditions inside a *nested function definition* reset the
    stack — the definition site does not gate the call's execution.

    Two reachability shapes are covered:

    * a collective lexically INSIDE a host-dependent branch (the
      condition-stack walk);
    * **early-return asymmetry**: ``if host...: return`` (or raise /
      continue / break) makes every later statement in the same suite
      reachable only by the hosts that did NOT take the branch — the
      same split brain with the collective OUTSIDE the branch, which
      the condition stack alone cannot see.  A host-dependent ``if``
      whose taken branch terminates while the other continues taints
      the rest of its suite.
    """
    if rel_suffix(rel) not in _COLLECTIVE_MODULES:
        return

    def visit(node: ast.AST, why: str | None) -> None:
        if isinstance(node, ast.Module):
            visit_suite(node.body, why)
            return
        if isinstance(node, ast.ClassDef):
            for expr in (*node.decorator_list, *node.bases,
                         *(kw.value for kw in node.keywords)):
                visit(expr, why)
            visit_suite(node.body, why)
            return
        if isinstance(node, _FUNC_NODES):
            if isinstance(node.body, list):
                visit_suite(node.body, None)
            else:  # lambda: body is a single expression
                visit(node.body, None)
            return
        if isinstance(node, ast.Call) and why is not None:
            callee = _collective_callee(node)
            if callee is not None:
                add(node, "collective-symmetry",
                    f"collective/barrier call '{callee}' is reachable "
                    f"only under a host-dependent condition ({why}): "
                    "hosts that don't take this branch never make the "
                    "matching call — a split-brain hang at pod scale. "
                    "Make the call unconditional (same sequence on every "
                    "host) or restructure so all hosts branch "
                    "identically")
        if isinstance(node, (ast.If, ast.While)):
            new_why = _host_dependent_why(node.test) or why
            visit(node.test, why)
            visit_suite(node.body, new_why)
            visit_suite(node.orelse, new_why)
            return
        if isinstance(node, ast.IfExp):
            new_why = _host_dependent_why(node.test) or why
            visit(node.test, why)
            visit(node.body, new_why)
            visit(node.orelse, new_why)
            return
        # every other statement suite walks suite-aware too, so a
        # host-gated continue/break/return INSIDE a loop / with / try
        # taints the rest of that suite (the shapes _suite_terminates
        # lists can only occur here)
        if isinstance(node, (ast.For, ast.AsyncFor)):
            visit(node.target, why)
            visit(node.iter, why)
            visit_suite(node.body, why)
            visit_suite(node.orelse, why)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                visit(item, why)
            visit_suite(node.body, why)
            return
        if isinstance(node, ast.Try):
            visit_suite(node.body, why)
            for h in node.handlers:
                visit_suite(h.body, why)
            visit_suite(node.orelse, why)
            visit_suite(node.finalbody, why)
            return
        for child in ast.iter_child_nodes(node):
            visit(child, why)

    def visit_suite(stmts: list, why: str | None) -> None:
        for stmt in stmts:
            visit(stmt, why)
            if why is None and isinstance(stmt, ast.If):
                host_why = _host_dependent_why(stmt.test)
                if host_why is None:
                    continue
                body_exits = _suite_terminates(stmt.body)
                else_exits = (
                    _suite_terminates(stmt.orelse) if stmt.orelse else False
                )
                # asymmetric continuation: one side leaves, the other
                # falls through — everything after this statement runs
                # on a host-dependent subset.  Both sides terminating
                # is symmetric (nothing after is reachable at all).
                if body_exits != else_exits:
                    why = (
                        "code after an early "
                        f"{'return' if body_exits else 'fall-through'} "
                        f"behind a host-dependent branch ({host_why})"
                    )

    visit(tree, None)


# ---------------------------------------------------------------------------
# recompile-hazard rule family
# ---------------------------------------------------------------------------


def _rule_recompile_shape_branch(
    tree, mod: _Module, traced: set[int], rel: str, add
) -> None:
    """Python branching on ``.shape``/``.dtype`` inside traced code:
    legal (shapes are Python values under trace) but it specializes the
    compiled program per input shape — every new shape silently
    recompiles, the exact steps/s cliff the pjit paper chases.  Where
    the dispatch is intentional (a fixed bucket grid the factory
    precompiles), suppress with a justification."""
    for node, enclosing in _iter_with_enclosing(tree, mod):
        if enclosing is None or id(enclosing.node) not in traced:
            continue
        if not isinstance(node, (ast.If, ast.While, ast.IfExp)):
            continue
        # a guard clause (body is a lone `raise`, no else) is a shape
        # ASSERTION: the other program variant doesn't exist, invalid
        # shapes just error — not the dispatch hazard this rule hunts
        if (
            isinstance(node, ast.If)
            and not node.orelse
            and len(node.body) == 1
            and isinstance(node.body[0], ast.Raise)
        ):
            continue
        attrs = sorted({
            n.attr
            for n in ast.walk(node.test)
            if isinstance(n, ast.Attribute) and n.attr in ("shape", "dtype")
        })
        if attrs:
            add(node, "recompile-shape-branch",
                f"branch on .{'/.'.join(attrs)} inside traced function "
                f"'{enclosing.name}': the Python branch specializes the "
                "compiled program per input shape/dtype, so every new "
                "shape recompiles silently (steps/s craters with no "
                "error); pad/bucket inputs, or keep the dispatch but "
                "bound the bucket set and precompile it")


def _mutable_globals(tree: ast.Module) -> dict[str, str]:
    """Module-level names bound to mutable containers (plus names
    reassigned through ``global``), with a short description each."""
    out: dict[str, str] = {}
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = [t for t in node.targets if isinstance(t, ast.Name)]
            value = node.value
        elif isinstance(node, ast.AnnAssign) and isinstance(
            node.target, ast.Name
        ):
            targets = [node.target]
            value = node.value
        else:
            continue
        if value is None:
            continue
        kind = None
        if isinstance(value, _MUTABLE_LITERALS):
            kind = type(value).__name__.lower().replace("comp", " comp")
        elif isinstance(value, ast.Call):
            d = _dotted(value.func)
            if d in _MUTABLE_CTORS:
                kind = f"{d}()"
        if kind:
            for t in targets:
                out[t.id] = kind
    for node in ast.walk(tree):
        if isinstance(node, ast.Global):
            for name in node.names:
                out.setdefault(name, "reassigned via 'global'")
    return out


def _rule_recompile_mutable_global(
    tree, mod: _Module, traced: set[int], rel: str, add
) -> None:
    """A traced function reading a mutable module global bakes its
    trace-time value into the compiled program: later mutations silently
    don't apply (or, if the object participates in a hash, force
    retraces).  Pass the value as an argument or make it an immutable
    constant."""
    mutables = _mutable_globals(tree)
    if not mutables:
        return
    seen: set[tuple[int, str]] = set()
    for node, enclosing in _iter_with_enclosing(tree, mod):
        if enclosing is None or id(enclosing.node) not in traced:
            continue
        if not isinstance(node, ast.Name) or not isinstance(
            node.ctx, ast.Load
        ):
            continue
        name = node.id
        if name not in mutables:
            continue
        # shadowed by a parameter anywhere up the lexical chain -> the
        # load reads the local, not the module global
        if any(
            name in outer.params
            for outer in mod.enclosing_chain(enclosing)
        ):
            continue
        key = (id(enclosing.node), name)
        if key in seen:
            continue
        seen.add(key)
        add(node, "recompile-mutable-global",
            f"traced function '{enclosing.name}' closes over mutable "
            f"module global '{name}' ({mutables[name]}): its value is "
            "baked in at trace time — later mutations silently don't "
            "apply to the compiled program; pass it as an argument or "
            "freeze it into an immutable constant")


def _static_decls(call: ast.Call) -> tuple[set[int], set[str]] | None:
    """(static positions, static names) a jit call declares, else None."""
    if _dotted(call.func) not in ("jax.jit", "jit"):
        return None
    nums: set[int] = set()
    names: set[str] = set()
    for kw in call.keywords:
        if kw.arg not in ("static_argnums", "static_argnames"):
            continue
        consts = (
            [kw.value] if isinstance(kw.value, ast.Constant)
            else list(ast.walk(kw.value))
        )
        for e in consts:
            if isinstance(e, ast.Constant):
                if isinstance(e.value, int):
                    nums.add(e.value)
                elif isinstance(e.value, str):
                    names.add(e.value)
    if not nums and not names:
        return None
    return nums, names


def _rule_recompile_static_args(tree, mod: _Module, rel: str, add) -> None:
    """Hazards at ``jit(..., static_argnums/static_argnames=...)``
    boundaries, seen from the call sites of the jitted wrapper:

    * an unhashable literal (list/dict/set) as a static arg — jit hashes
      static args for its compile cache, so this throws at dispatch;
    * a freshly-constructed object (``Cfg(...)`` at the call site) — a
      new instance per call identity-hashes, so the compile cache
      misses EVERY call and the program silently recompiles each step
      (the fresh-PRNGKey-as-static class of bug).  Value-hashed
      built-ins (``tuple(...)``/``frozenset(...)``) are fine.
    """
    jitted: dict[str, tuple[set[int], set[str]]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(
            node.value, ast.Call
        ):
            decls = _static_decls(node.value)
            if decls is not None:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        jitted[t.id] = decls
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    target = dec
                    if _is_partial(dec.func) and dec.args:
                        # @partial(jax.jit, static_argnames=...)
                        if _dotted(dec.args[0]) not in ("jax.jit", "jit"):
                            continue
                        target = ast.Call(
                            func=dec.args[0], args=[], keywords=dec.keywords
                        )
                    decls = _static_decls(target)
                    if decls is not None:
                        jitted[node.name] = decls
    if not jitted:
        return

    def check(arg: ast.AST, where: str) -> None:
        if isinstance(arg, _MUTABLE_LITERALS):
            add(arg, "recompile-unhashable-static",
                f"unhashable {type(arg).__name__.lower()} literal as the "
                f"static arg {where}: jit hashes static args for its "
                "compile cache — this raises at dispatch; pass a tuple/"
                "frozen structure (or make the arg traced)")
        elif isinstance(arg, ast.Call):
            d = _dotted(arg.func) or "<call>"
            if d in _VALUE_HASHED_CTORS or _is_partial(arg.func):
                return
            add(arg, "recompile-fresh-static",
                f"freshly-constructed '{d}(...)' as the static arg "
                f"{where}: a new instance per call identity-hashes, so "
                "the jit compile cache misses EVERY call — a silent "
                "recompile per step; construct it once at factory level "
                "(or use a value-hashed/immutable type)")

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not isinstance(
            node.func, ast.Name
        ):
            continue
        decls = jitted.get(node.func.id)
        if decls is None:
            continue
        nums, names = decls
        for i, arg in enumerate(node.args):
            if i in nums:
                check(arg, f"(position {i}) of '{node.func.id}'")
        for kw in node.keywords:
            if kw.arg in names:
                check(kw.value, f"'{kw.arg}=' of '{node.func.id}'")


# ---------------------------------------------------------------------------
# package-level rule: dead event kinds (needs every module's emits)
# ---------------------------------------------------------------------------


def _collect_emitted_kinds(trees) -> set[str]:
    kinds: set[str] = set()
    for tree in trees:
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _EMIT_ATTRS
            ):
                kind = _emit_kind_literal(node)
                if kind is not None:
                    kinds.add(kind)
    return kinds


def _rule_dead_event_kinds(
    trees, registry: Registry, events_rel: str, events_src: str | None
) -> list[Finding]:
    """Every EVENT_KINDS entry must be emitted somewhere in the package:
    a kind nothing emits is either dead weight or evidence the emitter
    was deleted while its dashboards still query the name.  Anchored at
    the registry line, so a justified keep is a suppression comment on
    that entry."""
    if not registry.event_kinds:
        return []
    emitted = _collect_emitted_kinds(trees)
    lines = (events_src or "").splitlines()
    findings: list[Finding] = []
    for kind in sorted(registry.event_kinds - emitted):
        line = registry.kind_lines.get(kind, 1)
        src_line = lines[line - 1] if 0 < line <= len(lines) else ""
        if suppressed(src_line, "obs-event-dead"):
            continue
        findings.append(Finding(
            events_rel, line, "obs-event-dead",
            f"event kind {kind!r} is registered in EVENT_KINDS but "
            "nothing in the package emits it; prune it (or suppress "
            "with a justification if an external emitter owns it)",
        ))
    return findings


def rel_suffix(rel: str) -> str:
    """'ddl_tpu/train/loop.py' -> 'train/loop.py' (module path within
    the package, for the per-module rule scopes)."""
    parts = Path(rel).parts
    if parts and parts[0] == "ddl_tpu":
        parts = parts[1:]
    return "/".join(parts)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def _run_rules(
    tree,
    mod: _Module,
    traced: set[int],
    rel: str,
    src: str,
    registry: Registry,
    reasons: dict[int, str] | None = None,
) -> list[Finding]:
    """Every per-module rule over one parsed module, with ``traced``
    supplied by the caller (local inference for ``lint_file``, the
    cross-module program inference for ``lint_package``)."""
    lines = src.splitlines()
    findings: list[Finding] = []

    def add(node: ast.AST, rule: str, message: str) -> None:
        line = getattr(node, "lineno", 1)
        src_line = lines[line - 1] if 0 < line <= len(lines) else ""
        if suppressed(src_line, rule):
            return
        findings.append(Finding(rel, line, rule, message))

    _rule_traced_interop(tree, mod, traced, rel, add, reasons)
    _rule_excepts(tree, rel, add)
    _rule_compat(tree, rel, add)
    _rule_obs_events(tree, registry, rel, add)
    _rule_pspec(tree, mod, rel, add)
    _rule_pspec_hand_rolled(tree, mod, rel, add)
    _rule_donation(tree, mod, rel, add)
    _rule_exit_intent(tree, mod, rel, add)
    _rule_collective_symmetry(tree, mod, rel, add)
    _rule_recompile_shape_branch(tree, mod, traced, rel, add)
    _rule_recompile_mutable_global(tree, mod, traced, rel, add)
    _rule_recompile_static_args(tree, mod, rel, add)
    return findings


def lint_file(
    path: str | Path, repo_root: str | Path, registry: Registry
) -> list[Finding]:
    """Single-file run (explicit CLI paths, editor-on-save): every
    per-module rule with module-local traced inference — no cross-module
    propagation, no package-level rules."""
    path = Path(path)
    try:
        rel = path.relative_to(repo_root).as_posix()
    except ValueError:  # explicit file outside the repo (CLI paths arg)
        rel = path.as_posix()
    src = path.read_text()
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(rel, e.lineno or 1, "syntax-error", str(e.msg))]
    mod = _Module(tree)
    traced = _infer_traced(mod)
    return sorted(_run_rules(tree, mod, traced, rel, src, registry))


def lint_package(
    package_root: str | Path,
    files: list[Path] | None = None,
    graph=None,
) -> list[Finding]:
    """Run every AST rule over the package with WHOLE-PROGRAM traced-set
    inference: the import/call graph (``callgraph.CallGraph``) is always
    built over the full package, so a host sync hidden behind a helper
    in another module is attributed correctly even when ``files``
    narrows the *reported* set (``lint --changed``).  Package-level
    rules (dead event kinds) run only on full-package reports.
    ``package_root`` is the ``ddl_tpu`` directory; paths in findings are
    relative to its parent (the repo root).  A caller that already built
    the ``graph`` (the ``--changed`` CLI computes the closure from one)
    passes it in to avoid a second full parse — it MUST reflect the
    current on-disk sources."""
    from ddl_tpu.analysis.callgraph import CallGraph

    package_root = Path(package_root)
    repo_root = package_root.parent
    registry = load_registry(package_root)
    if graph is None:
        graph = CallGraph(package_root)
    traced, reasons = infer_traced_program(graph)
    full_run = files is None
    if files is None:
        files = sorted(package_root.rglob("*.py"))
    findings: list[Finding] = []
    for f in files:
        f = Path(f)
        try:
            rel = f.relative_to(repo_root).as_posix()
        except ValueError:
            rel = f.as_posix()
        info = graph.by_rel.get(rel)
        if info is None:
            # outside the package, or a syntax error the graph skipped:
            # single-file fallback (reports the syntax error)
            findings.extend(lint_file(f, repo_root, registry))
            continue
        mod_reasons = {
            node_id: why
            for (mname, node_id), why in reasons.items()
            if mname == info.name
        }
        findings.extend(_run_rules(
            info.tree, info.mod, traced[info.name], rel, info.src,
            registry, mod_reasons,
        ))
    events_rel = f"{package_root.name}/obs/events.py"
    if full_run or any(
        Path(f).name == "events.py" for f in files
    ):
        events_info = graph.by_rel.get(events_rel)
        findings.extend(_rule_dead_event_kinds(
            [i.tree for i in graph.modules.values()],
            registry,
            events_rel,
            events_info.src if events_info is not None else None,
        ))
    return sorted(findings)
