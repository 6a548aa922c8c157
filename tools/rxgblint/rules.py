"""The rxgblint rule implementations: one AST pass per module.

Scope notes (what the rules can and cannot see) — also documented in the
README rule catalog:

* **Traced-code detection** (DET001 time.*, SYNC001) is lexical: a function
  is "traced" when it is passed to a jax tracing entry point
  (``jit``/``shard_map``/``vmap``/``pmap``/``scan``/
  ``cond``/...) directly, by name within the same module, or via a ``jit``
  decorator — plus everything lexically nested inside such a function.
  Closures returned from one function and traced in another (the engine's
  ``_round_closures`` pattern) are NOT detected; the rules under-approximate
  rather than flood engine host code with false positives.
* **LOCK001** is lexical too: an access is "guarded" when it sits inside
  ``with self.<lock>`` in the same function. The repo's convention for
  caller-holds-the-lock helpers is a ``_locked`` name suffix (e.g.
  ``_percentile_locked``): such methods are exempt from the guard check,
  and in exchange every CALL to a ``*_locked`` method must itself sit
  inside a ``with self.<lock>`` block — the contract is enforced on both
  ends.
"""

import ast
from typing import Dict, List, Optional, Set

from tools.rxgblint import catalog
from tools.rxgblint.findings import Finding

# jax tracing entry points: a function passed into one of these executes
# under trace, where host-side effects are hazards
TRACER_CALLS = frozenset({
    "jit", "shard_map", "vmap", "pmap", "scan",
    "while_loop", "fori_loop", "cond", "switch", "checkpoint", "remat",
    "grad", "value_and_grad", "custom_jvp", "custom_vjp",
})

# SPMD001 cares about communicating collectives only (axis_index is
# rank-divergence-safe); SPMD002 validates the axis arg of everything
SPMD001_CALLS = (catalog.JAX_COLLECTIVES - {"axis_index"}) | catalog.COLLECTIVE_WRAPPERS

_TIME_FNS = frozenset({
    "time", "perf_counter", "monotonic", "process_time", "time_ns",
    "perf_counter_ns", "monotonic_ns",
})
_PY_RANDOM_FNS = frozenset({
    "random", "randint", "randrange", "choice", "choices", "shuffle",
    "sample", "uniform", "gauss", "seed", "getrandbits", "betavariate",
    "expovariate", "normalvariate",
})
_NP_RANDOM_OK = frozenset({
    "RandomState", "default_rng", "Generator", "SeedSequence", "PCG64",
    "Philox",
})
_SET_CONSUMERS = frozenset({
    "list", "tuple", "enumerate", "array", "asarray", "stack",
    "concatenate", "fromiter",
})
_SYNC_BUILTINS = frozenset({"float", "bool"})
_EXEMPT_METHODS = frozenset({"__init__", "__post_init__", "__new__", "__del__"})


def _terminal(node: ast.AST) -> str:
    """Terminal identifier of a Name/Attribute chain ('' otherwise)."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _chain(node: ast.AST) -> List[str]:
    """['np', 'random', 'rand'] for ``np.random.rand``; [] when dynamic."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return []


# shared with the lock-owning-class catalog (catalog.lock_owning_classes is
# the single definition rxgbrace's instrumenter reuses)
_is_self_attr = catalog._is_self_attr
_mentions = catalog._mentions


def _rank_tainted(cond: ast.AST) -> bool:
    """Does a branch condition depend on rank-/shard-identity?"""
    for sub in ast.walk(cond):
        if isinstance(sub, (ast.Name, ast.Attribute)):
            ident = _terminal(sub)
            if ident and catalog.RANK_TAINT_RE.search(ident.lower()):
                return True
        if isinstance(sub, ast.Call) and _terminal(sub.func) in catalog.RANK_TAINT_CALLS:
            return True
    return False


class _Module:
    """Parsed module plus the derived maps every rule shares."""

    def __init__(self, source: str, path: str, root: str = catalog.REPO_ROOT):
        self.source = source
        self.path = path
        self.root = root
        self.tree = ast.parse(source, filename=path)
        self.parent: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node
        self.scopes = self._scope_map()
        self.traced = self._traced_functions()

    # -- scopes -------------------------------------------------------------

    def _scope_map(self) -> Dict[ast.AST, str]:
        """node -> dotted qualname of its enclosing class/function chain."""
        scopes: Dict[ast.AST, str] = {}

        def visit(node: ast.AST, stack: List[str]):
            for child in ast.iter_child_nodes(node):
                if isinstance(
                    child,
                    (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
                ):
                    scopes[child] = ".".join(stack) if stack else "<module>"
                    visit(child, stack + [child.name])
                elif isinstance(child, ast.Lambda):
                    scopes[child] = ".".join(stack) if stack else "<module>"
                    visit(child, stack + ["<lambda>"])
                else:
                    visit(child, stack)

        visit(self.tree, [])
        return scopes

    def scope_of(self, node: ast.AST) -> str:
        """Dotted qualname of the scope containing ``node``."""
        cur = node
        while cur is not None:
            if isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda)
            ):
                base = self.scopes.get(cur, "<module>")
                name = getattr(cur, "name", "<lambda>")
                return name if base == "<module>" else f"{base}.{name}"
            cur = self.parent.get(cur)
        return "<module>"

    def nearest_function(self, node: ast.AST):
        cur = self.parent.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                return cur
            cur = self.parent.get(cur)
        return None

    def nearest_named_function(self, node: ast.AST):
        cur = self.parent.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parent.get(cur)
        return None

    # -- traced-function detection -----------------------------------------

    def _direct_defs(self, owner: ast.AST) -> List[ast.AST]:
        """FunctionDefs declared directly in ``owner``'s scope (descending
        into if/try/with blocks but not into nested functions/classes)."""
        defs: List[ast.AST] = []

        def visit(node: ast.AST):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    defs.append(child)
                elif not isinstance(child, (ast.ClassDef, ast.Lambda)):
                    visit(child)

        visit(owner)
        return defs

    def _resolve_local_def(self, node: ast.AST, name: str):
        """The FunctionDef bound to ``name`` at ``node``, per lexical scoping
        (climbing enclosing functions up to the module; class bodies don't
        leak method names into nested scopes)."""
        cur = self.parent.get(node)
        while cur is not None:
            if isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.Module)
            ):
                for fn in self._direct_defs(cur):
                    if fn.name == name:
                        return fn
                if isinstance(cur, ast.Module):
                    return None
            elif isinstance(cur, ast.ClassDef):
                # method names are not visible as bare names from inside
                # other methods; skip past the class scope
                pass
            cur = self.parent.get(cur)
        return None

    def _traced_functions(self) -> Set[ast.AST]:
        traced: Set[ast.AST] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and _terminal(node.func) in TRACER_CALLS:
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    if isinstance(arg, ast.Lambda):
                        traced.add(arg)
                    elif isinstance(arg, ast.Name):
                        fn = self._resolve_local_def(node, arg.id)
                        if fn is not None:
                            traced.add(fn)
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    tail = _terminal(dec.func if isinstance(dec, ast.Call) else dec)
                    if tail == "jit" or (
                        isinstance(dec, ast.Call)
                        and tail == "partial"
                        and _mentions(dec, frozenset({"jit"}))
                    ):
                        traced.add(node)
        # lexical nesting: everything inside a traced function is traced
        out: Set[ast.AST] = set(traced)
        for fn in traced:
            for sub in ast.walk(fn):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                    out.add(sub)
        return out

    def in_traced(self, node: ast.AST) -> bool:
        cur = self.parent.get(node)
        while cur is not None:
            if cur in self.traced:
                return True
            cur = self.parent.get(cur)
        return False

    # -- helpers ------------------------------------------------------------

    def finding(self, rule: str, node: ast.AST, message: str) -> Finding:
        return Finding(
            rule=rule,
            path=self.path,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            message=message,
            scope=self.scope_of(node),
        )


# ---------------------------------------------------------------------------
# SPMD001 — collectives under rank-dependent Python control flow
# ---------------------------------------------------------------------------


def check_spmd001(mod: _Module) -> List[Finding]:
    findings = []
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and _terminal(node.func) in SPMD001_CALLS):
            continue
        fn_boundary = mod.nearest_function(node)
        cur, prev = mod.parent.get(node), node
        while cur is not None and cur is not fn_boundary:
            cond = None
            if isinstance(cur, (ast.If, ast.While)):
                # only the guarded body/orelse diverges; the test itself runs
                # on every rank
                if prev is not cur.test:
                    cond = cur.test
            elif isinstance(cur, ast.IfExp) and prev is not cur.test:
                cond = cur.test
            if cond is not None and _rank_tainted(cond):
                findings.append(mod.finding(
                    "SPMD001", node,
                    f"collective {_terminal(node.func)!r} under rank-"
                    f"dependent control flow: ranks that skip this branch "
                    f"never join the collective (cluster hang); hoist the "
                    f"collective or use lax.cond/where",
                ))
                break
            prev, cur = cur, mod.parent.get(cur)
    return findings


# ---------------------------------------------------------------------------
# SPMD002 — collective axis names must come from the mesh-axis catalog
# ---------------------------------------------------------------------------


def check_spmd002(mod: _Module) -> List[Finding]:
    findings = []
    axes = catalog.mesh_axes(mod.root)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _terminal(node.func)
        if name not in catalog.JAX_COLLECTIVES:
            continue
        # jax.lax collectives take the axis as the 2nd positional arg
        # (axis_index takes it as the 1st) or as axis_name=
        axis_arg = None
        for kw in node.keywords:
            if kw.arg == "axis_name":
                axis_arg = kw.value
        if axis_arg is None:
            pos = 0 if name == "axis_index" else 1
            if len(node.args) > pos:
                axis_arg = node.args[pos]
        if axis_arg is None:
            continue
        literals = []
        if isinstance(axis_arg, ast.Constant) and isinstance(axis_arg.value, str):
            literals = [axis_arg.value]
        elif isinstance(axis_arg, (ast.Tuple, ast.List)):
            literals = [
                e.value for e in axis_arg.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            ]
        elif isinstance(axis_arg, (ast.Name, ast.Attribute)):
            ident = _terminal(axis_arg)
            consts = dict(catalog.axis_constants(mod.root))
            if ident in consts:
                # declared AXIS_* constant: resolve to its value and
                # validate like a literal (one source of truth with the
                # Mesh constructors — see constants.py)
                if consts[ident] not in axes:
                    findings.append(mod.finding(
                        "SPMD002", node,
                        f"collective {name!r} axis constant {ident} "
                        f"resolves to {consts[ident]!r}, not a declared "
                        f"mesh axis: {sorted(axes)}",
                    ))
                continue
            if "axis" not in ident.lower():
                findings.append(mod.finding(
                    "SPMD002", node,
                    f"collective {name!r} axis comes from opaque variable "
                    f"{ident!r}; pass a literal from the mesh-axis catalog "
                    f"{sorted(axes)} or a parameter named axis_name",
                ))
            continue
        else:
            findings.append(mod.finding(
                "SPMD002", node,
                f"collective {name!r} axis is a computed expression; use a "
                f"literal from the mesh-axis catalog {sorted(axes)}",
            ))
            continue
        for lit in literals:
            if lit not in axes:
                findings.append(mod.finding(
                    "SPMD002", node,
                    f"collective {name!r} names unknown mesh axis {lit!r}; "
                    f"declared axes: {sorted(axes)}",
                ))
    return findings


# ---------------------------------------------------------------------------
# DET001 — nondeterminism sources
# ---------------------------------------------------------------------------


def check_det001(mod: _Module) -> List[Finding]:
    findings = []
    salts = catalog.salt_values(mod.root)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _chain(node.func)
        tail = _terminal(node.func)
        # (a) module-level RNGs: random.random() / np.random.rand()
        if chain[:1] == ["random"] and len(chain) == 2 and tail in _PY_RANDOM_FNS:
            findings.append(mod.finding(
                "DET001", node,
                f"module-level random.{tail}() draws from global unseeded "
                f"state; use a seeded random.Random(seed) instance",
            ))
        if (
            len(chain) == 3
            and chain[0] in ("np", "numpy")
            and chain[1] == "random"
            and tail not in _NP_RANDOM_OK
        ):
            findings.append(mod.finding(
                "DET001", node,
                f"np.random.{tail}() draws from global RNG state; use a "
                f"seeded np.random.RandomState/default_rng instance",
            ))
        # (b) wall clock inside traced code
        if chain[:1] == ["time"] and tail in _TIME_FNS and mod.in_traced(node):
            findings.append(mod.finding(
                "DET001", node,
                f"time.{tail}() inside traced code: the value freezes at "
                f"trace time and differs across compiles (nondeterministic "
                f"program text)",
            ))
        # (c) PRNGKey must come from a seed
        if tail in ("PRNGKey", "key") and chain[:2] == ["jax", "random"] or (
            tail == "PRNGKey" and chain[-2:-1] == ["random"]
        ):
            arg = node.args[0] if node.args else None
            for kw in node.keywords:
                if kw.arg == "seed":
                    arg = kw.value
            ok = (
                isinstance(arg, ast.Constant) and isinstance(arg.value, int)
            ) or (
                isinstance(arg, (ast.Name, ast.Attribute))
                and "seed" in _terminal(arg).lower()
            ) or (
                isinstance(arg, ast.Call)
                and "seed" in _terminal(arg.func).lower()
            )
            if arg is not None and not ok:
                findings.append(mod.finding(
                    "DET001", node,
                    "PRNGKey seeded from a non-seed expression; route "
                    "through params.seed (plus SALT_* fold domains) so "
                    "runs stay bitwise reproducible",
                ))
        # (d) fold_in with a magic literal outside the SALT_* domains
        if tail == "fold_in" and len(node.args) >= 2:
            data = node.args[1]
            if isinstance(data, ast.Constant) and isinstance(data.value, int):
                if data.value not in salts:
                    findings.append(mod.finding(
                        "DET001", node,
                        f"fold_in literal {data.value:#x} is not a declared "
                        f"SALT_* domain; add a SALT_* constant (ops/grow.py) "
                        f"so fold domains provably never collide",
                    ))
    # (e) unsorted set iteration feeding ordered consumers
    for node in ast.walk(mod.tree):
        is_set = isinstance(node, (ast.Set, ast.SetComp)) or (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id in ("set", "frozenset")
        )
        if not is_set:
            continue
        parent = mod.parent.get(node)
        flagged = False
        if isinstance(parent, (ast.For, ast.AsyncFor)) and parent.iter is node:
            flagged = True
        elif isinstance(parent, ast.comprehension) and parent.iter is node:
            flagged = True
        elif (
            isinstance(parent, ast.Call)
            and node in parent.args
            and _terminal(parent.func) in _SET_CONSUMERS
        ):
            flagged = True
        if flagged:
            findings.append(mod.finding(
                "DET001", node,
                "iterating a set in order-sensitive context: set order "
                "varies across processes (PYTHONHASHSEED); wrap in sorted()",
            ))
    return findings


# ---------------------------------------------------------------------------
# SYNC001 — hidden host<->device syncs in traced code
# ---------------------------------------------------------------------------


def check_sync001(mod: _Module) -> List[Finding]:
    findings = []
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and mod.in_traced(node)):
            continue
        tail = _terminal(node.func)
        chain = _chain(node.func)
        msg = None
        if (
            isinstance(node.func, ast.Name)
            and tail in _SYNC_BUILTINS
            and node.args
            # float("inf")/bool(0)-style literal args can never be traced
            # values — no sync, don't force a pragma on idiomatic sentinels
            and not all(isinstance(a, ast.Constant) for a in node.args)
        ):
            msg = f"{tail}() on a traced value forces a host sync"
        elif isinstance(node.func, ast.Attribute) and tail == "item":
            msg = ".item() on a traced value forces a host sync"
        elif (
            len(chain) >= 2
            and chain[0] in ("np", "numpy", "onp")
            and tail in ("asarray", "array")
        ):
            msg = (
                f"{'.'.join(chain)}() materializes a traced value on host "
                f"(use jnp.{tail})"
            )
        elif tail in ("device_get", "block_until_ready"):
            msg = f"{tail}() inside traced code forces a host sync"
        if msg:
            findings.append(mod.finding(
                "SYNC001", node,
                msg + "; inside a round closure this serializes the "
                "device pipeline every round",
            ))
    return findings


# ---------------------------------------------------------------------------
# LOCK001 — shared state outside the lock in lock-owning classes
# ---------------------------------------------------------------------------


def _lock_attrs_of_class(cls: ast.ClassDef) -> Set[str]:
    """Delegates to the shared catalog extraction — LOCK001's notion of
    "lock-owning" and the rxgbrace instrumenter's are the same function."""
    return set(catalog.lock_attr_kinds(cls))


def _held_locks(cls: ast.ClassDef, locks: Set[str]) -> Dict[ast.AST, frozenset]:
    """Map every node to the frozenset of lock attrs held at that point
    (lexically nested ``with self.<lock>`` blocks accumulate). Tracking
    WHICH locks are held — not just "some lock" — is what lets the check
    catch state guarded by lock A being read under unrelated lock B: the
    wrong-lock torn read is the same bug as no lock at all."""
    held: Dict[ast.AST, frozenset] = {}

    def visit(node: ast.AST, holding: frozenset):
        held[node] = holding
        acquired = set()
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                attr = _is_self_attr(item.context_expr)
                if attr in locks:
                    acquired.add(attr)
        if acquired:
            holding = holding | acquired
        for child in ast.iter_child_nodes(node):
            visit(child, holding)

    visit(cls, frozenset())
    return held


def check_lock001(mod: _Module) -> List[Finding]:
    findings = []
    for cls in ast.walk(mod.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        locks = _lock_attrs_of_class(cls)
        if not locks:
            continue
        held = _held_locks(cls, locks)

        # shared-mutable set: self._x assigned under a lock anywhere, or
        # assigned inside a *_locked (caller-holds-lock) method. Track the
        # lock sets held at guarded writes: their intersection is the
        # attr's owning lock(s), so a read under an unrelated lock can be
        # flagged as the torn read it is.
        shared: Set[str] = set()
        write_locks: Dict[str, frozenset] = {}
        for node in ast.walk(cls):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            for tgt in targets:
                # `self._seen[i] += 1` mutates self._seen just as much as
                # `self._seen = [...]` rebinds it
                if isinstance(tgt, ast.Subscript):
                    tgt = tgt.value
                attr = _is_self_attr(tgt)
                if not attr or not attr.startswith("_") or attr in locks:
                    continue
                fn = mod.nearest_named_function(node)
                in_locked_helper = fn is not None and fn.name.endswith("_locked")
                holding = held.get(node, frozenset())
                if holding or in_locked_helper:
                    shared.add(attr)
                    if holding:
                        write_locks[attr] = (
                            write_locks[attr] & holding
                            if attr in write_locks else holding
                        )

        if not shared:
            continue

        for node in ast.walk(cls):
            # unguarded call of a *_locked helper: contract breach on the
            # caller side
            if (
                isinstance(node, ast.Call)
                and (attr := _is_self_attr(node.func))
                and attr.endswith("_locked")
                and not held.get(node)
            ):
                fn = mod.nearest_named_function(node)
                if fn is not None and (
                    fn.name in _EXEMPT_METHODS or fn.name.endswith("_locked")
                ):
                    continue
                findings.append(mod.finding(
                    "LOCK001", node,
                    f"self.{attr}() requires the caller to hold "
                    f"self.{sorted(locks)[0]} (the _locked suffix contract) "
                    f"but is called outside any `with` on it",
                ))
                continue
            if not isinstance(node, ast.Attribute):
                continue
            attr = _is_self_attr(node)
            if attr not in shared:
                continue
            fn = mod.nearest_named_function(node)
            if fn is None:
                continue
            if fn.name in _EXEMPT_METHODS or fn.name.endswith("_locked"):
                continue
            holding = held.get(node, frozenset())
            owner = write_locks.get(attr, frozenset())
            access = "write to" if isinstance(node.ctx, (ast.Store, ast.Del)) else "read of"
            if not holding:
                findings.append(mod.finding(
                    "LOCK001", node,
                    f"unguarded {access} self.{attr} in {cls.name}."
                    f"{fn.name}: this attribute is mutated under "
                    f"self.{sorted(owner or locks)[0]} elsewhere, so "
                    f"lock-free access can tear; guard it or move it into "
                    f"a *_locked helper",
                ))
            elif owner and not (holding & owner):
                # holding SOME lock of the class, just not the one that
                # guards this attribute's writes — same torn read/lost
                # update as no lock at all, but it reads as safe
                findings.append(mod.finding(
                    "LOCK001", node,
                    f"{access} self.{attr} in {cls.name}.{fn.name} holds "
                    f"self.{sorted(holding)[0]} but the attribute's writes "
                    f"are guarded by self.{sorted(owner)[0]}: the wrong "
                    f"lock does not serialize against them",
                ))
    return findings


# ---------------------------------------------------------------------------
# FAULT001 — fault-site strings must come from faults.SITES
# ---------------------------------------------------------------------------

FAULT_CALLS = frozenset({"fire", "fire_file", "plan_targets"})


def collect_fault_sites_used(mod: _Module) -> Set[str]:
    used: Set[str] = set()
    for node in ast.walk(mod.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in FAULT_CALLS
            and _terminal(node.func.value) == "faults"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            used.add(node.args[0].value)
    return used


def check_fault001(mod: _Module) -> List[Finding]:
    findings = []
    sites = set(catalog.fault_sites(mod.root))
    for node in ast.walk(mod.tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in FAULT_CALLS
            and _terminal(node.func.value) == "faults"
        ):
            continue
        if not node.args or not (
            isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            findings.append(mod.finding(
                "FAULT001", node,
                f"faults.{node.func.attr}() site must be a string literal "
                f"so plans are statically checkable against faults.SITES",
            ))
            continue
        site = node.args[0].value
        if sites and site not in sites:
            findings.append(mod.finding(
                "FAULT001", node,
                f"unknown fault site {site!r}; faults.SITES declares "
                f"{sorted(sites)} — a typo here makes chaos plans silently "
                f"no-op",
            ))
    return findings


# ---------------------------------------------------------------------------
# OBS001 — span/event names: static literals from the trace-name catalog
# ---------------------------------------------------------------------------

OBS_EMITTERS = frozenset({"event", "span", "add_span"})


def collect_trace_literals(mod: _Module) -> Set[str]:
    """Every string literal in the module that is a catalogued trace name
    (loose on purpose: names fed through local emit() helpers still count
    toward reverse coverage)."""
    names = catalog.trace_names(mod.root)
    found: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if node.value in names:
                found.add(node.value)
    return found


def _static_name_options(arg: ast.AST):
    """The finite set of literal names an expression can evaluate to, or
    None when dynamic. Accepts bare literals and conditional expressions
    over literals (``"world.shrink" if cond else "world.grow"``)."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return [arg.value]
    if isinstance(arg, ast.IfExp):
        body = _static_name_options(arg.body)
        orelse = _static_name_options(arg.orelse)
        if body is not None and orelse is not None:
            return body + orelse
    return None


def check_obs001(mod: _Module) -> List[Finding]:
    if mod.path.replace("\\", "/").endswith("obs/trace.py"):
        return []  # the catalog module itself
    findings = []
    names = catalog.trace_names(mod.root)
    for node in ast.walk(mod.tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in OBS_EMITTERS
            and node.args
        ):
            continue
        arg = node.args[0]
        options = _static_name_options(arg)
        if options is not None:
            for name in options:
                if not catalog.TRACE_NAME_RE.match(name):
                    findings.append(mod.finding(
                        "OBS001", node,
                        f"span/event name {name!r} violates the lowercase "
                        f"dotted-identifier shape the timeline schema pins",
                    ))
                elif names and name not in names:
                    findings.append(mod.finding(
                        "OBS001", node,
                        f"span/event name {name!r} is not in obs.trace."
                        f"TRACE_NAMES; add it to the catalog (and the README "
                        f"span table) or fix the typo",
                    ))
        elif isinstance(arg, ast.JoinedStr):
            findings.append(mod.finding(
                "OBS001", node,
                "f-string span/event name: emit one catalogued literal per "
                "variant so the timeline stays statically greppable",
            ))
        else:
            findings.append(mod.finding(
                "OBS001", node,
                "dynamic span/event name: the schema validator and the "
                "trace-name catalog cannot pin names it cannot see; pass a "
                "literal (or baseline this helper with a justification)",
            ))
    return findings


# ---------------------------------------------------------------------------
# EXP001 — __all__ must resolve; required public API must be exported
# ---------------------------------------------------------------------------


def _all_strings(tree: ast.Module) -> List[ast.Constant]:
    """Every string constant contributed to __all__ (=, +=, .extend)."""
    out: List[ast.Constant] = []

    def strings_of(node):
        return [
            e for e in getattr(node, "elts", [])
            if isinstance(e, ast.Constant) and isinstance(e.value, str)
        ]

    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            if any(
                isinstance(t, ast.Name) and t.id == "__all__"
                for t in node.targets
            ):
                out.extend(strings_of(node.value))
        elif isinstance(node, ast.AugAssign):
            if isinstance(node.target, ast.Name) and node.target.id == "__all__":
                out.extend(strings_of(node.value))
        elif (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "extend"
            and _terminal(node.func.value) == "__all__"
            and node.args
        ):
            out.extend(strings_of(node.args[0]))
    return out


def _bound_names(tree: ast.Module) -> Set[str]:
    """Names bound at MODULE scope only. A whole-tree walk would count a
    function-local as a module binding and let a broken ``__all__`` entry
    lint clean — the exact AttributeError this rule exists to catch.
    Module-level control flow (``if TYPE_CHECKING``, try/except import
    fallbacks, conditional defs) still binds at module scope, so those
    blocks are descended; function/class bodies are new scopes and are
    not (the def/class *name* itself does bind)."""
    bound: Set[str] = set()

    def names_in(target: ast.AST) -> None:
        for sub in ast.walk(target):
            if isinstance(sub, ast.Name):
                bound.add(sub.id)

    def visit(stmts) -> None:
        for node in stmts:
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                for alias in node.names:
                    bound.add(alias.asname or alias.name.split(".")[0])
            elif isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                bound.add(node.name)
            elif isinstance(node, ast.Assign):
                for tgt in node.targets:
                    names_in(tgt)
            elif isinstance(node, ast.AnnAssign) and isinstance(
                node.target, ast.Name
            ):
                bound.add(node.target.id)
            elif isinstance(node, (ast.If, ast.While)):
                visit(node.body)
                visit(node.orelse)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                names_in(node.target)
                visit(node.body)
                visit(node.orelse)
            elif isinstance(node, ast.Try):
                visit(node.body)
                for handler in node.handlers:
                    if handler.name:
                        bound.add(handler.name)
                    visit(handler.body)
                visit(node.orelse)
                visit(node.finalbody)
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if item.optional_vars is not None:
                        names_in(item.optional_vars)
                visit(node.body)

    visit(tree.body)
    return bound


def check_exp001(mod: _Module) -> List[Finding]:
    if not mod.path.replace("\\", "/").endswith("__init__.py"):
        return []
    exported = _all_strings(mod.tree)
    if not exported:
        return []
    findings = []
    bound = _bound_names(mod.tree)
    for const in exported:
        if const.value not in bound:
            findings.append(mod.finding(
                "EXP001", const,
                f"__all__ exports {const.value!r} but the module never "
                f"binds it; `from pkg import *` raises AttributeError",
            ))
    is_top = mod.path.replace("\\", "/").endswith(
        f"{catalog.PACKAGE}/__init__.py"
    )
    if is_top:
        names = {c.value for c in exported}
        missing = sorted(catalog.REQUIRED_EXPORTS - names)
        if missing:
            findings.append(mod.finding(
                "EXP001", mod.tree.body[0] if mod.tree.body else mod.tree,
                f"required public symbols missing from __all__: {missing} "
                f"(API surface added by earlier PRs must stay exported)",
            ))
    return findings


ALL_CHECKS = (
    check_spmd001,
    check_spmd002,
    check_det001,
    check_sync001,
    check_lock001,
    check_fault001,
    check_obs001,
    check_exp001,
)
