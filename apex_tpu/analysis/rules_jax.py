"""J-series rules: JAX/TPU pipeline hazards.

These encode the throughput discipline the training stack already follows
by hand (``training/aql.py:153-163``, ``training/r2d2.py:265-275``): donated
step buffers, no host round-trips inside compiled code, split-don't-reuse
PRNG keys, trace-once jit.  Each rule's behavioral contract is its fixture
pair in ``tests/test_analysis.py``.
"""

from __future__ import annotations

import ast
import re

from apex_tpu.analysis.core import (Finding, ModuleContext, Rule, call_name,
                                    is_jit_expr, register)

# -- shared helpers ---------------------------------------------------------


def _is_step_name(name: str) -> bool:
    """Names that take large donated state as leading args: the train /
    fused / ingest step family.  Policy fns (params reused across calls)
    deliberately don't match."""
    n = name.lower().lstrip("_")
    if "ingest" in n:
        return True
    return "step" in n and any(t in n for t in
                               ("train", "fused", "update", "multi"))


def _has_donation(call: ast.Call) -> bool:
    return any(k.arg in ("donate_argnums", "donate_argnames")
               for k in call.keywords)


def _attr_root(node: ast.AST) -> str | None:
    """Leftmost name of an attribute chain: ``np.asarray`` -> np."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


_NUMPY_ALIASES = {"np", "numpy", "onp"}
_JNP_ALIASES = {"jnp", "jax"}


def _loops_between(ctx: ModuleContext, node: ast.AST, stop: ast.AST | None):
    """Enclosing For/While nodes of ``node`` up to (exclusive) ``stop`` or
    the enclosing function boundary.  A For whose ``iter``/``target`` holds
    the node doesn't count — that expression evaluates once, not per
    iteration (a While ``test`` does re-evaluate, so it counts)."""
    out = []
    child = node
    for a in ctx.ancestors(node):
        if a is stop:
            break
        if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
            break
        if isinstance(a, (ast.For, ast.AsyncFor)):
            if child is not a.iter and child is not a.target:
                out.append(a)
        elif isinstance(a, ast.While):
            out.append(a)
        child = a
    return out


# -- J001 -------------------------------------------------------------------


@register
class JitMissingDonation(Rule):
    id = "J001"
    name = "jit-missing-donation"
    why = ("Un-donated jit step buffers keep the old state alive across the "
           "update and double learner HBM.")
    fix = ("Pass donate_argnums for the state buffers the step consumes and "
           "rebind them from the result.")
    description = ("jit-wrapped train/ingest step without donate_argnums: "
                   "the old state buffers stay live across the update and "
                   "double learner HBM")

    def check(self, ctx: ModuleContext) -> list[Finding]:
        out = []
        for node in ctx.nodes(ast.Call):
            if not is_jit_expr(node.func):
                continue
            if not node.args or _has_donation(node):
                continue
            tgt = node.args[0]
            if isinstance(tgt, ast.Name):
                name = tgt.id
            elif isinstance(tgt, ast.Attribute):
                name = tgt.attr
            else:
                continue                  # jit(factory(...)): not a step ref
            if is_jit_expr(tgt):          # the partial(jax.jit, ...) form
                continue
            if _is_step_name(name):
                out.append(ctx.finding(
                    self, node,
                    f"jax.jit({name}) without donate_argnums — donate the "
                    f"state args or the update keeps both copies in HBM"))
        # decorator form: @jax.jit / @partial(jax.jit, ...) on a step def
        for fn in ctx.functions:
            if not _is_step_name(fn.name):
                continue
            for dec in fn.decorator_list:
                if not is_jit_expr(dec):
                    continue
                if isinstance(dec, ast.Call) and _has_donation(dec):
                    continue
                out.append(ctx.finding(
                    self, dec,
                    f"@jit on step '{fn.name}' without donate_argnums — "
                    f"donate the state args or the update keeps both "
                    f"copies in HBM"))
        return out


# -- J002 -------------------------------------------------------------------


@register
class HostSyncInJit(Rule):
    id = "J002"
    name = "host-sync-in-jit"
    why = ("A host conversion on a traced value inside jit breaks tracing or "
           "forces a device sync.")
    fix = ("Keep the math in jnp inside the jitted scope; materialize on the "
           "host after dispatch.")
    description = ("float()/int()/bool()/.item()/np.asarray() on a traced "
                   "value inside a jitted function: forces a host-device "
                   "sync per call and serializes the pipeline")

    _BUILTINS = {"float", "int", "bool"}
    _METHODS = {"item", "tolist"}
    _NUMPY_FUNCS = {"asarray", "array"}

    def check(self, ctx: ModuleContext) -> list[Finding]:
        out = []
        for node in ctx.nodes(ast.Call):
            fn = ctx.in_jitted_scope(node)
            if fn is None:
                continue
            f = node.func
            if (isinstance(f, ast.Name) and f.id in self._BUILTINS
                    and node.args
                    and not all(isinstance(a, ast.Constant)
                                for a in node.args)):
                out.append(ctx.finding(
                    self, node,
                    f"{f.id}() inside jitted '{fn.name}' pulls the value "
                    f"to host — use jnp ops (or hoist out of the jit)"))
            elif (isinstance(f, ast.Attribute) and f.attr in self._METHODS
                    and not node.args):
                out.append(ctx.finding(
                    self, node,
                    f".{f.attr}() inside jitted '{fn.name}' pulls the "
                    f"value to host — keep it a traced array"))
            elif (isinstance(f, ast.Attribute)
                    and f.attr in self._NUMPY_FUNCS
                    and _attr_root(f) in _NUMPY_ALIASES):
                out.append(ctx.finding(
                    self, node,
                    f"np.{f.attr}() inside jitted '{fn.name}' materializes "
                    f"on host — use jnp.{f.attr} or hoist out of the jit"))
        return out


# -- J003 -------------------------------------------------------------------


@register
class TracedPythonBranch(Rule):
    id = "J003"
    name = "traced-python-branch"
    why = ("Python control flow on a traced value errors at trace time or "
           "silently retraces per branch.")
    fix = ("Branch with lax.cond/lax.select (or jnp.where) so the choice "
           "compiles into the program.")
    description = ("Python if/while on a traced value inside a jitted "
                   "function: either a tracer-bool error at trace time or "
                   "a silent retrace per branch — use lax.cond/lax.select")

    # parameters with these fragments are static config, not traced arrays
    _STATIC_HINTS = ("name", "axis", "mode", "dtype", "shape", "static",
                     "interpret", "config", "cfg", "spec")

    def _is_static_param(self, name: str) -> bool:
        n = name.lower()
        return n == "self" or any(h in n for h in self._STATIC_HINTS)

    def check(self, ctx: ModuleContext) -> list[Finding]:
        out = []
        for node in ctx.nodes(ast.If, ast.While):
            fn = ctx.in_jitted_scope(node)
            if fn is None:
                continue
            why = self._traced_test(node.test, fn)
            if why:
                kind = "if" if isinstance(node, ast.If) else "while"
                out.append(ctx.finding(
                    self, node,
                    f"Python {kind} on {why} inside jitted '{fn.name}' — "
                    f"use jax.lax.cond/select (or make the arg static)"))
        return out

    def _traced_test(self, test: ast.AST, fn) -> str | None:
        # identity tests and isinstance are static dispatch — fine
        for n in ast.walk(test):
            if isinstance(n, ast.Compare) and all(
                    isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops):
                return None
            if (isinstance(n, ast.Call)
                    and call_name(n) in ("isinstance", "hasattr",
                                         "getattr", "len")):
                return None
        params = {a.arg for a in (fn.args.args + fn.args.kwonlyargs
                                  + fn.args.posonlyargs)
                  if not self._is_static_param(a.arg)}
        for n in ast.walk(test):
            if (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
                    and _attr_root(n.func) in _JNP_ALIASES):
                return f"a {_attr_root(n.func)}.* result"
            if isinstance(n, ast.Compare):
                sides = [n.left] + list(n.comparators)
                for s in sides:
                    if isinstance(s, ast.Name) and s.id in params:
                        return f"traced arg '{s.id}'"
                    # ts.step > 0: a field of a traced arg is traced too
                    if isinstance(s, ast.Attribute) \
                            and _attr_root(s) in params:
                        return f"traced arg '{_attr_root(s)}'"
        return None


# -- J004 -------------------------------------------------------------------


#: split is NOT here: it needs a random-ish receiver (_is_key_source) or
#: str.split unpacks would mint phantom keys
_KEY_SOURCE_ATTRS = {"PRNGKey", "fold_in"}
# params opt into tracking by JAX's `key` convention only — `rng` is the
# numpy.random.Generator convention, where reuse is the whole point
_KEY_NAME_RE = re.compile(r"key", re.IGNORECASE)


def _is_key_source(call: ast.Call) -> bool:
    """jax.random.split / .key / .PRNGKey / .fold_in (any random alias)."""
    f = call.func
    if not isinstance(f, ast.Attribute):
        return False
    if f.attr == "split":
        # require a random-ish receiver, like `.key` below: plain
        # ``path.split(":")`` is str.split — its unpack targets are not
        # PRNG keys (the engine used to flag any later loop use of them)
        recv = f.value
        recv_name = (recv.attr if isinstance(recv, ast.Attribute)
                     else recv.id if isinstance(recv, ast.Name) else "")
        return ("random" in recv_name
                or recv_name in ("jr", "jrandom", "rng"))
    if f.attr in _KEY_SOURCE_ATTRS:
        return True
    if f.attr == "key":
        # jax.random.key(...) but not cfg.key(...): require a random-ish
        # receiver
        recv = f.value
        recv_name = (recv.attr if isinstance(recv, ast.Attribute)
                     else recv.id if isinstance(recv, ast.Name) else "")
        return "random" in recv_name or recv_name in ("jr", "jrandom")
    return False


@register
class PRNGKeyReuse(Rule):
    id = "J004"
    name = "prng-key-reuse"
    why = ("A PRNG key consumed twice correlates draws that must be "
           "independent.")
    fix = ("jax.random.split the key and consume each subkey exactly once "
           "(split per loop iteration).")
    description = ("a PRNG key consumed more than once (or consumed inside "
                   "a loop without a per-iteration split): correlated "
                   "randomness silently corrupts exploration and "
                   "prioritized sampling")

    def check(self, ctx: ModuleContext) -> list[Finding]:
        out = []
        for fn in ctx.functions:
            # skip nested defs: the enclosing function's scan covers them
            # (their free-variable key uses belong to the outer scope)
            if ctx.enclosing_function(fn) is not None:
                continue
            out.extend(_scan_function_keys(self, ctx, fn))
        return out


def _terminates(body) -> bool:
    """A statement list that cannot fall through."""
    return any(isinstance(s, (ast.Return, ast.Raise, ast.Break,
                              ast.Continue)) for s in body)


def _scan_function_keys(rule: Rule, ctx: ModuleContext, fn) -> list[Finding]:
    """Source-order scan of one function (including nested defs): track key
    variables, count consumptions, flag the second use and any
    loop-enclosed use whose key was made outside the loop."""
    findings: list[Finding] = []
    # name -> (assignment node, uses-so-far)
    keys: dict[str, list] = {}
    for a in fn.args.args + fn.args.kwonlyargs + fn.args.posonlyargs:
        if _KEY_NAME_RE.search(a.arg):
            keys[a.arg] = [fn, 0]

    def names_in(node: ast.AST, bound: frozenset = frozenset()):
        """Free names in an argument expression.  Does NOT descend into
        nested calls (``env.step(act(obs, k))`` charges k to ``act``
        alone) and drops names rebound by comprehension targets or lambda
        params along the way (``{k: float(v) for k, v in m.items()}``
        consumes no outer ``k``)."""
        out: set[str] = set()
        if isinstance(node, ast.Name):
            if node.id not in bound:
                out.add(node.id)
        elif isinstance(node, ast.Call):
            pass                      # every call owns its own args
        elif isinstance(node, ast.Subscript):
            pass                      # keys[i] picks one subkey from a
            #                           pre-split batch — not a reuse
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            b2 = set(bound)
            for g in node.generators:
                b2 |= {t.id for t in ast.walk(g.target)
                       if isinstance(t, ast.Name)}
            for c in ast.iter_child_nodes(node):
                out |= names_in(c, frozenset(b2))
        elif isinstance(node, ast.Lambda):
            b2 = frozenset(bound | {p.arg for p in
                                    (node.args.args + node.args.kwonlyargs
                                     + node.args.posonlyargs)})
            out |= names_in(node.body, b2)
        else:
            for c in ast.iter_child_nodes(node):
                out |= names_in(c, bound)
        return out

    def consume(name: str, at: ast.AST) -> None:
        entry = keys.get(name)
        if entry is None:
            return
        entry[1] += 1
        assigned_at, uses = entry
        if uses >= 2:
            findings.append(ctx.finding(
                rule, at,
                f"PRNG key '{name}' consumed again without "
                f"jax.random.split — every consumer needs a fresh subkey"))
            entry[1] = 1          # re-arm so each extra reuse flags once
            return
        loops = _loops_between(ctx, at, None)
        assign_loops = set(map(id, _loops_between(ctx, assigned_at, None)))
        if any(id(lp) not in assign_loops for lp in loops):
            findings.append(ctx.finding(
                rule, at,
                f"PRNG key '{name}' consumed inside a loop but created "
                f"outside it — split a fresh subkey per iteration"))
            entry[1] = 0          # one report per site, not one per use

    def comp_bound(at: ast.AST, name: str) -> bool:
        """True when ``name`` is rebound by an enclosing comprehension
        target or lambda parameter — it shadows the outer key there."""
        for a in ctx.ancestors(at):
            if isinstance(a, (ast.ListComp, ast.SetComp, ast.DictComp,
                              ast.GeneratorExp)):
                for g in a.generators:
                    if any(isinstance(t, ast.Name) and t.id == name
                           for t in ast.walk(g.target)):
                        return True
            elif isinstance(a, ast.Lambda):
                if any(p.arg == name for p in
                       (a.args.args + a.args.kwonlyargs
                        + a.args.posonlyargs)):
                    return True
            elif isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
        return False

    def visit_call(node: ast.Call) -> None:
        if _is_key_source(node):
            return                # split/fold_in refresh, not a consumption
        if call_name(node) in ("getattr", "hasattr", "isinstance", "len",
                               "type", "id"):
            return                # introspection reads no PRNG material
        for arg in list(node.args) + [k.value for k in node.keywords]:
            for name in names_in(arg):
                if name in keys and not comp_bound(node, name):
                    consume(name, node)

    def assign_targets(targets, value) -> None:
        from_key_source = isinstance(value, ast.Call) \
            and _is_key_source(value)
        for t in targets:
            elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
            for e in elts:
                if not isinstance(e, ast.Name):
                    continue
                if from_key_source or (e.id in keys):
                    if from_key_source:
                        keys[e.id] = [e, 0]
                    else:
                        keys.pop(e.id, None)    # overwritten by non-key

    def walk_expr(node: ast.AST) -> None:
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                visit_call(n)

    def visit_stmt(stmt: ast.AST) -> None:
        if isinstance(stmt, ast.Assign):
            walk_expr(stmt.value)
            assign_targets(stmt.targets, stmt.value)
        elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            if stmt.value is not None:
                walk_expr(stmt.value)
            assign_targets([stmt.target], stmt.value or stmt)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            walk_expr(stmt.iter)
            for s in stmt.body + stmt.orelse:
                visit_stmt(s)
        elif isinstance(stmt, ast.While):
            walk_expr(stmt.test)
            for s in stmt.body + stmt.orelse:
                visit_stmt(s)
        elif isinstance(stmt, ast.If):
            # if/else branches are mutually exclusive: one consumption in
            # each branch is one consumption at runtime, not two.  A
            # branch that terminates (return/raise/...) contributes
            # nothing to the fall-through path.
            walk_expr(stmt.test)
            snap = {k: list(v) for k, v in keys.items()}
            for s in stmt.body:
                visit_stmt(s)
            after_body = {k: list(v) for k, v in keys.items()}
            keys.clear()
            keys.update({k: list(v) for k, v in snap.items()})
            for s in stmt.orelse:
                visit_stmt(s)
            if not _terminates(stmt.body):
                for name, entry in after_body.items():
                    if name in keys:
                        keys[name][1] = max(keys[name][1], entry[1])
                    else:
                        keys[name] = entry
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                walk_expr(item.context_expr)
            for s in stmt.body:
                visit_stmt(s)
        elif isinstance(stmt, ast.Try):
            for s in (stmt.body + stmt.orelse + stmt.finalbody
                      + [h for hh in stmt.handlers for h in hh.body]):
                visit_stmt(s)
        elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested def: free-variable key uses count against the outer
            # scope, but its own params SHADOW same-named outer keys and
            # get their own fresh reuse budget
            params = (stmt.args.args + stmt.args.kwonlyargs
                      + stmt.args.posonlyargs)
            shadowed = {a.arg: keys.pop(a.arg) for a in params
                        if a.arg in keys}
            own = [a.arg for a in params if _KEY_NAME_RE.search(a.arg)]
            for name in own:
                keys[name] = [stmt, 0]
            for s in stmt.body:
                visit_stmt(s)
            for name in own:
                keys.pop(name, None)
            keys.update(shadowed)
        elif isinstance(stmt, (ast.Return, ast.Expr)):
            if getattr(stmt, "value", None) is not None:
                walk_expr(stmt.value)
        else:
            walk_expr(stmt)

    for s in fn.body:
        visit_stmt(s)
    return findings


# -- J006 -------------------------------------------------------------------


_TIMING_CALLS = {"perf_counter", "monotonic", "perf_counter_ns",
                 "monotonic_ns", "time", "time_ns"}


def _is_trace_context(expr: ast.AST) -> bool:
    """``with trace(...)`` / ``profiling.trace(...)`` /
    ``jax.profiler.trace(...)``, or a trace ring's ``span(...)`` (one
    ring event and one profiler annotation over the block) — the
    sanctioned profiling scopes."""
    if not isinstance(expr, ast.Call):
        return False
    name = call_name(expr) or ""
    return name in ("trace", "span") or name.endswith("_trace")


@register
class HostSyncInHotLoop(Rule):
    id = "J006"
    name = "host-sync-in-hot-loop"
    why = ("A blocking device read in the hot loop serializes dispatch "
           "against the device each step.")
    fix = ("Drop the sync from the steady-state path; read results at "
           "episode/log boundaries.")
    description = ("block_until_ready()/jax.device_get() inside a host-side "
                   "loop outside profiling scopes: a full device drain per "
                   "iteration serializes the async-dispatch pipeline the "
                   "learner hot path depends on")

    def _sync_kind(self, node: ast.Call) -> str | None:
        f = node.func
        if not isinstance(f, ast.Attribute):
            return None
        if f.attr == "block_until_ready":
            # jax.block_until_ready(x) and x.block_until_ready() alike
            return ("jax.block_until_ready()"
                    if _attr_root(f) in _JNP_ALIASES and node.args
                    else ".block_until_ready()")
        if f.attr == "device_get" and _attr_root(f) in _JNP_ALIASES:
            return "jax.device_get()"
        return None

    def _in_profiling_scope(self, ctx: ModuleContext, node: ast.AST,
                            loops: list) -> bool:
        # (a) lexically under `with trace(...)`: an explicit profiler
        # capture is allowed to fence the device
        for a in ctx.ancestors(node):
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
            if isinstance(a, (ast.With, ast.AsyncWith)):
                if any(_is_trace_context(item.context_expr)
                       for item in a.items):
                    return True
        # (b) a measurement harness: some enclosing loop's body reads the
        # clock (bench-style `t0 = perf_counter(); ...; block_until_ready`)
        # — timing a device fence is the one legitimate hot-loop sync
        for loop in loops:
            for sub in ast.walk(loop):
                if (isinstance(sub, ast.Call)
                        and call_name(sub) in _TIMING_CALLS):
                    return True
        return False

    def check(self, ctx: ModuleContext) -> list[Finding]:
        out = []
        for node in ctx.nodes(ast.Call):
            kind = self._sync_kind(node)
            if kind is None:
                continue
            if ctx.in_jitted_scope(node):
                continue                     # J002's territory
            loops = _loops_between(ctx, node, None)
            if not loops:
                continue
            if self._in_profiling_scope(ctx, node, loops):
                continue
            out.append(ctx.finding(
                self, node,
                f"{kind} inside a host loop — a device drain per "
                f"iteration stalls async dispatch; stage it off the hot "
                f"loop (training/ingest_pipeline) or wrap the "
                f"measurement in a profiling trace scope"))
        return out


# -- J007 -------------------------------------------------------------------


@register
class DevicePutInJit(Rule):
    id = "J007"
    name = "device-put-in-jit"
    why = ("device_put inside compiled code is at best a redundant copy, at "
           "worst a per-call transfer.")
    fix = ("Stage operands onto the device before the dispatch and pass "
           "device arrays in.")
    description = ("jax.device_put inside jitted/shard_map scope: a "
                   "placement request inside compiled code is at best a "
                   "redundant copy and at worst a per-call transfer — "
                   "stage operands before the dispatch (the ingest "
                   "pipeline's staging thread exists for exactly this)")

    _PUT_ATTRS = {"device_put", "device_put_sharded",
                  "device_put_replicated"}

    def check(self, ctx: ModuleContext) -> list[Finding]:
        out = []
        for node in ctx.nodes(ast.Call):
            f = node.func
            if not (isinstance(f, ast.Attribute)
                    and f.attr in self._PUT_ATTRS
                    and _attr_root(f) in _JNP_ALIASES):
                continue
            fn = ctx.in_jitted_scope(node)
            if fn is None:
                continue
            out.append(ctx.finding(
                self, node,
                f"jax.{f.attr} inside jitted scope '{fn.name}' — "
                f"placement belongs before the jit/shard_map boundary; "
                f"stage the operand host-side "
                f"(training/ingest_pipeline.py staging thread)"))
        return out


# -- J008 -------------------------------------------------------------------


_MATERIALIZE_NUMPY = {"asarray", "array"}


def _jit_callable_names(ctx: ModuleContext) -> set[str]:
    """Names that dispatch compiled code when called: targets assigned from
    ``jax.jit(...)`` (``self.policy = jax.jit(...)`` -> ``policy``),
    functions passed to ``jax.jit`` by name, and ``@jit``-decorated defs.
    Deliberately NOT the transitive jitted-scope closure — calling a
    helper that jitted code also calls is a plain host call."""
    out: set[str] = set()
    for node in ctx.nodes(ast.Call):
        if not is_jit_expr(node.func):
            continue
        if node.args:
            tgt = node.args[0]
            if isinstance(tgt, ast.Name):
                out.add(tgt.id)
            elif isinstance(tgt, ast.Attribute):
                out.add(tgt.attr)
        parent = ctx.parents.get(node)
        if isinstance(parent, ast.Assign):
            for t in parent.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
                elif isinstance(t, ast.Attribute):
                    out.add(t.attr)
    for fn in ctx.functions:
        if any(is_jit_expr(d) for d in fn.decorator_list):
            out.add(fn.name)
    return out


def _is_timed_context(expr: ast.AST) -> bool:
    """``with phase(...)`` / ``x.phase(...)`` or a trace scope — explicit
    wait accounting (utils/profiling.PhaseTimer), the sanctioned place to
    block on a device result."""
    if _is_trace_context(expr):
        return True
    return isinstance(expr, ast.Call) and call_name(expr) == "phase"


@register
class EagerJitMaterialize(Rule):
    id = "J008"
    name = "eager-jit-materialize"
    why = ("Materializing a jit result inline blocks the dispatch pipeline on "
           "the transfer.")
    fix = ("Keep results on device; convert to host types only where they are "
           "consumed.")
    description = ("np.asarray()/jax.device_get() materializing a jitted "
                   "result in a host step loop with the value consumed "
                   "more than one statement later: the blocking sync "
                   "serializes the dispatch pipeline against host work "
                   "that could overlap it — defer materialization to the "
                   "consumption site (the double-buffered actor step, "
                   "actors/vector.py)")

    def _materializer_args(self, call: ast.Call) -> list | None:
        f = call.func
        if not isinstance(f, ast.Attribute) or not call.args:
            return None
        if f.attr in _MATERIALIZE_NUMPY and _attr_root(f) in _NUMPY_ALIASES:
            return list(call.args)
        if f.attr == "device_get" and _attr_root(f) in _JNP_ALIASES:
            return list(call.args)
        return None

    @staticmethod
    def _stmt_position(ctx: ModuleContext, stmt: ast.AST):
        parent = ctx.parents.get(stmt)
        for field in ("body", "orelse", "finalbody"):
            seq = getattr(parent, field, None)
            if isinstance(seq, list) and stmt in seq:
                return seq, seq.index(stmt)
        return None, None

    def _in_timed_scope(self, ctx: ModuleContext, node: ast.AST) -> bool:
        for a in ctx.ancestors(node):
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
            if isinstance(a, (ast.With, ast.AsyncWith)):
                if any(_is_timed_context(item.context_expr)
                       for item in a.items):
                    return True
        return False

    def check(self, ctx: ModuleContext) -> list[Finding]:
        jit_names = _jit_callable_names(ctx)
        if not jit_names:
            return []
        out: list[Finding] = []
        for fn in ctx.functions:
            if ctx.in_jitted_scope(fn):
                continue                      # host-side rule
            # values returned by a jit dispatch in this function
            device_vars: set[str] = set()
            for node in ast.walk(fn):
                if (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)
                        and call_name(node.value) in jit_names):
                    for t in node.targets:
                        elts = (t.elts if isinstance(t, (ast.Tuple,
                                                         ast.List))
                                else [t])
                        device_vars.update(e.id for e in elts
                                           if isinstance(e, ast.Name))
            for stmt in ast.walk(fn):
                if not isinstance(stmt, ast.Assign):
                    continue
                found = self._check_assign(ctx, fn, stmt, device_vars,
                                           jit_names)
                if found is not None:
                    out.append(found)
        return out

    def _check_assign(self, ctx, fn, stmt: ast.Assign, device_vars,
                      jit_names):
        calls = (stmt.value.elts
                 if isinstance(stmt.value, (ast.Tuple, ast.List))
                 else [stmt.value])
        sync = None
        for c in calls:
            if not isinstance(c, ast.Call):
                continue
            args = self._materializer_args(c)
            if args is None:
                continue
            refs_device = any(
                (isinstance(n, ast.Name) and n.id in device_vars)
                or (isinstance(n, ast.Call) and call_name(n) in jit_names)
                for a in args for n in ast.walk(a))
            if refs_device:
                sync = c
                break
        if sync is None or self._in_timed_scope(ctx, stmt):
            return None
        targets = {n.id for t in stmt.targets for n in ast.walk(t)
                   if isinstance(n, ast.Name)}
        seq, idx = self._stmt_position(ctx, stmt)
        if seq is None:
            return None
        consumer = None
        for dist, later in enumerate(seq[idx + 1:], start=1):
            if any(isinstance(n, ast.Name) and n.id in targets
                   for n in ast.walk(later)):
                consumer = (dist, later)
                break
        if consumer is None:
            return None
        dist, later = consumer
        if dist <= 1:
            return None                  # materialized at the use site
        hot = (bool(_loops_between(ctx, stmt, None))
               or isinstance(later, (ast.For, ast.AsyncFor, ast.While)))
        if not hot:
            return None
        return ctx.finding(
            self, sync,
            f"jitted result materialized {dist} statements before its "
            f"first use — the blocking sync runs before host work it "
            f"could overlap; defer np.asarray/device_get to the "
            f"consumption site (or wrap a deliberate wait in a "
            f"PhaseTimer.phase scope)")


# -- J009 -------------------------------------------------------------------


_QUEUE_NAME_RE = re.compile(r"(queue|_q$|^q$)", re.IGNORECASE)

#: calls that force a HOST value out of a device result — putting one of
#: these on the queue ships plain numpy/python, which is the point
_J009_MATERIALIZERS = {"asarray", "array", "device_get", "int", "float",
                       "bool", "tolist", "item"}


@register
class DeviceArrayOnMpQueue(Rule):
    id = "J009"
    name = "device-array-on-mp-queue"
    why = ("Queue.put pickles a device array, forcing an implicit "
           "device->host copy and sync.")
    fix = ("Materialize with np.asarray/jax.device_get first and enqueue the "
           "host array.")
    description = ("mp.Queue put of a jitted/device result without a host "
                   "materialize: Queue.put pickles the object, forcing an "
                   "implicit device->host copy (and a device sync) per "
                   "chunk inside the worker loop — np.asarray/device_get "
                   "it once at the producer and ship host data")

    @staticmethod
    def _queue_receiver(call: ast.Call) -> bool:
        f = call.func
        if not (isinstance(f, ast.Attribute)
                and f.attr in ("put", "put_nowait")):
            return False
        recv = f.value
        name = None
        if isinstance(recv, ast.Name):
            name = recv.id
        elif isinstance(recv, ast.Attribute):
            name = recv.attr
        return bool(name and _QUEUE_NAME_RE.search(name))

    @staticmethod
    def _materialized(ctx: ModuleContext, name_node: ast.AST,
                      put: ast.Call) -> bool:
        """True when the device name is wrapped in a materializer call
        somewhere between itself and the put() — ``q.put(np.asarray(x))``
        ships host data and is fine."""
        for a in ctx.ancestors(name_node):
            if a is put:
                return False
            if isinstance(a, ast.Call):
                base = call_name(a)
                if base in _J009_MATERIALIZERS:
                    return True
        return False

    def check(self, ctx: ModuleContext) -> list[Finding]:
        jit_names = _jit_callable_names(ctx)
        if not jit_names:
            return []
        out = []
        for fn in ctx.functions:
            if ctx.in_jitted_scope(fn):
                continue
            device_vars: set[str] = set()
            rematerialized: set[str] = set()
            for node in ast.walk(fn):
                if not isinstance(node, ast.Assign):
                    continue
                if isinstance(node.value, ast.Call) \
                        and call_name(node.value) in jit_names:
                    for t in node.targets:
                        elts = (t.elts if isinstance(t, (ast.Tuple,
                                                         ast.List))
                                else [t])
                        device_vars.update(e.id for e in elts
                                           if isinstance(e, ast.Name))
                elif isinstance(node.value, ast.Call) \
                        and call_name(node.value) in _J009_MATERIALIZERS:
                    # `host = np.asarray(dev)` re-binds a host value:
                    # putting THAT name is fine
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            rematerialized.add(t.id)
            if not device_vars:
                continue
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and self._queue_receiver(node)):
                    continue
                offenders = [
                    n for arg in node.args for n in ast.walk(arg)
                    if isinstance(n, ast.Name) and n.id in device_vars
                    and n.id not in rematerialized
                    and not self._materialized(ctx, n, node)]
                if offenders:
                    names = ", ".join(sorted({n.id for n in offenders}))
                    out.append(ctx.finding(
                        self, node,
                        f"device result(s) {names} put on an mp queue "
                        f"without a host materialize — the pickle in "
                        f"Queue.put forces a device->host copy + sync per "
                        f"message; np.asarray/device_get at the producer "
                        f"and ship host data"))
        return out


# -- J010 -------------------------------------------------------------------


#: span/ring emission calls of the obs plane (apex_tpu/obs) — host-side
#: observability primitives that record NOTHING per call once traced
_OBS_EMIT_NAMES = {"stamp", "stamp_spans", "mark_send"}
_OBS_RING_METHODS = {"complete", "complete_wall", "instant", "span"}


@register
class HostClockInJit(Rule):
    id = "J010"
    name = "host-clock-in-jit"
    why = ("time.time() under jit bakes the trace-time clock into the "
           "compiled program as a constant.")
    fix = "Read clocks on the host and pass timestamps in as arguments."
    description = ("time.time()/time.perf_counter()/time.monotonic() (or an "
                   "obs-plane span/ring emission) inside jit/shard_map "
                   "trace scope: the clock reads at TRACE time, so every "
                   "call sees the same frozen timestamp — and a span "
                   "stamped there records nothing per step.  Hoist the "
                   "measurement to the host loop around the dispatch "
                   "(utils/profiling, apex_tpu/obs)")

    def _clock_read(self, node: ast.Call) -> str | None:
        f = node.func
        if isinstance(f, ast.Name) and f.id in _TIMING_CALLS:
            return f"{f.id}()"
        if (isinstance(f, ast.Attribute) and f.attr in _TIMING_CALLS
                and _attr_root(f) == "time"):
            return f"time.{f.attr}()"
        return None

    def _obs_emit(self, node: ast.Call) -> str | None:
        f = node.func
        name = call_name(node) or ""
        if name in _OBS_EMIT_NAMES:
            return f"{name}()"
        if (isinstance(f, ast.Attribute) and f.attr in _OBS_RING_METHODS):
            recv = f.value
            recv_name = (recv.attr if isinstance(recv, ast.Attribute)
                         else recv.id if isinstance(recv, ast.Name) else "")
            if "ring" in recv_name.lower():
                return f"{recv_name}.{f.attr}()"
        return None

    def check(self, ctx: ModuleContext) -> list[Finding]:
        out = []
        for node in ctx.nodes(ast.Call):
            fn = ctx.in_jitted_scope(node)
            if fn is None:
                continue
            what = self._clock_read(node) or self._obs_emit(node)
            if what is None:
                continue
            out.append(ctx.finding(
                self, node,
                f"{what} inside jitted scope '{fn.name}' reads the host "
                f"clock at trace time — the compiled program replays one "
                f"frozen timestamp per compile; measure around the "
                f"dispatch on the host loop instead"))
        return out


# -- J011 -------------------------------------------------------------------


#: the canonical fleet mesh axes, as declared by
#: apex_tpu.parallel.mesh.make_mesh — modules that import from that
#: module inherit these as their declared axis vocabulary
_CANONICAL_MESH_AXES = frozenset({"dp", "tp"})

_SPEC_CTORS = {"P", "PartitionSpec"}
_SHARD_MAP_NAMES = {"shard_map", "shard_map_compat", "pjit"}


@register
class ShardingAnnotationDrift(Rule):
    id = "J011"
    name = "sharding-annotation-drift"
    why = ("A PartitionSpec axis name no declared mesh axis matches silently "
           "degrades to replication.")
    fix = ("Name axes from the declared mesh ('dp'/'tp' in parallel/mesh.py) "
           "or extend the mesh.")
    description = ("a PartitionSpec axis name in pjit/shard_map "
                   "in/out shardings that no declared mesh axis matches "
                   "(parallel/mesh.py declares ('dp', 'tp')): the spec "
                   "silently stops sharding — or errors at dispatch — "
                   "when the annotation drifts from the mesh")

    def _declared_axes(self, ctx: ModuleContext) -> frozenset[str] | None:
        """Axis names this module's meshes declare: literal axis-name
        tuples in ``Mesh(...)`` constructions, plus the canonical
        ``make_mesh`` axes when the module uses apex_tpu.parallel.mesh.
        None = no mesh vocabulary in scope -> the rule stays silent (it
        judges drift, not style)."""
        axes: set[str] = set()
        canonical = False
        for node in ctx.nodes(ast.ImportFrom, ast.Call):
            if isinstance(node, ast.ImportFrom):
                if node.module and node.module.endswith("parallel.mesh"):
                    canonical = True
            elif isinstance(node, ast.Call):
                name = call_name(node)
                if name == "make_mesh":
                    canonical = True
                elif name == "Mesh":
                    for arg in list(node.args) + [k.value
                                                  for k in node.keywords]:
                        if isinstance(arg, (ast.Tuple, ast.List)):
                            names = [e.value for e in arg.elts
                                     if isinstance(e, ast.Constant)
                                     and isinstance(e.value, str)]
                            if names and len(names) == len(arg.elts):
                                axes.update(names)
        if canonical:
            axes.update(_CANONICAL_MESH_AXES)
        return frozenset(axes) if axes else None

    def _spec_axis_names(self, call: ast.Call):
        """(axis_name, node) pairs of the string constants a
        P/PartitionSpec construction mentions (nested tuples included:
        ``P(("dp", "tp"))`` shards one dim over both axes)."""
        for arg in list(call.args) + [k.value for k in call.keywords]:
            for n in ast.walk(arg):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    yield n.value, n

    def _annotation_scope(self, ctx: ModuleContext,
                          call: ast.Call) -> str | None:
        """The sharding-annotation surface ``call`` sits on, or None.
        Surfaces: in_specs/out_specs of shard_map (+compat) and
        in_shardings/out_shardings of jit/pjit — directly, or via a
        NamedSharding wrapping this spec anywhere (a NamedSharding is
        always a placement against a concrete mesh)."""
        for a in ctx.ancestors(call):
            if isinstance(a, ast.Call):
                name = call_name(a) or ""
                if name == "NamedSharding":
                    return "NamedSharding"
                if name in _SHARD_MAP_NAMES or is_jit_expr(a.func):
                    for kw in a.keywords:
                        if kw.arg in ("in_specs", "out_specs",
                                      "in_shardings", "out_shardings") \
                                and call in ast.walk(kw.value):
                            return f"{name}({kw.arg}=...)"
        return None

    def check(self, ctx: ModuleContext) -> list[Finding]:
        declared = self._declared_axes(ctx)
        if declared is None:
            return []
        out = []
        for node in ctx.nodes(ast.Call):
            if call_name(node) not in _SPEC_CTORS:
                continue
            scope = self._annotation_scope(ctx, node)
            if scope is None:
                continue
            for axis, at in self._spec_axis_names(node):
                if axis not in declared:
                    out.append(ctx.finding(
                        self, at,
                        f"PartitionSpec axis {axis!r} in {scope} matches "
                        f"no declared mesh axis {sorted(declared)} — the "
                        f"annotation drifted from the mesh "
                        f"(parallel/mesh.py); rename the axis or declare "
                        f"it on the Mesh"))
        return out


# -- J005 -------------------------------------------------------------------


@register
class JitInLoop(Rule):
    id = "J005"
    name = "jit-in-loop"
    why = ("jax.jit inside a loop builds a fresh callable per iteration, "
           "retracing every time.")
    fix = ("Hoist the jit to construction time and call the cached callable "
           "in the loop.")
    description = ("jax.jit(...) invoked inside a loop body: builds a fresh "
                   "wrapper (and usually retraces) every iteration — hoist "
                   "the jitted callable out of the loop")

    def check(self, ctx: ModuleContext) -> list[Finding]:
        out = []
        for node in ctx.nodes(ast.Call):
            if not is_jit_expr(node.func):
                continue
            if _loops_between(ctx, node, None):
                out.append(ctx.finding(
                    self, node,
                    "jax.jit called inside a loop body — hoist it; each "
                    "call builds a new wrapper and retraces"))
        return out


# -- J014 -------------------------------------------------------------------


@register
class HostNumpyOpInScannedEnv(Rule):
    id = "J014"
    name = "host-numpy-op-in-scanned-env"
    why = ("Host numpy inside a scanned env step runs per step on the host, "
           "defeating the scan.")
    fix = "Express the step in jnp so lax.scan keeps the rollout on device."
    description = ("np.* / float() / .item() reachable from a function "
                   "passed to lax.scan (a scanned env/rollout body, "
                   "training/anakin.py discipline): host numpy executes at "
                   "TRACE time — a TracerError at best, a silently frozen "
                   "per-compile constant at worst.  Use jnp ops inside the "
                   "compiled rollout; hoist genuine host work out of the "
                   "scan")

    _BUILTINS = {"float", "int", "bool"}

    def _scanned_functions(self, ctx: ModuleContext) -> set:
        """FunctionDefs reachable from a ``lax.scan``/``jax.lax.scan``
        body argument: named callees, every call inside an inline lambda
        body, nested defs, and the transitive same-module call graph
        (the jitted-scope closure's discipline, re-rooted at scan)."""
        seeds: set[str] = set()
        for node in ctx.nodes(ast.Call):
            if not node.args:
                continue
            f = node.func
            if not (isinstance(f, ast.Attribute) and f.attr == "scan"
                    and _attr_root(f) in ("lax", "jax")):
                continue
            tgt = node.args[0]
            if isinstance(tgt, ast.Name):
                seeds.add(tgt.id)
            elif isinstance(tgt, ast.Attribute):
                seeds.add(tgt.attr)
            elif isinstance(tgt, ast.Lambda):
                # `lambda c, x: self._step(...)` — everything the lambda
                # calls runs inside the scanned program
                for sub in ast.walk(tgt):
                    if isinstance(sub, ast.Call):
                        nm = call_name(sub)
                        if nm:
                            seeds.add(nm)
        if not seeds:
            return set()
        scanned = {fn for fn in ctx.functions if fn.name in seeds}
        by_name: dict[str, list] = {}
        for fn in ctx.functions:
            by_name.setdefault(fn.name, []).append(fn)
        changed = True
        while changed:
            changed = False
            for fn in list(scanned):
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    for cand in by_name.get(call_name(node) or "", []):
                        if cand not in scanned:
                            scanned.add(cand)
                            changed = True
        return scanned

    @staticmethod
    def _static_arg(a: ast.AST) -> bool:
        """Constants, attribute chains (``self.B`` — static config), and
        tuples thereof: legitimate trace-time shape/constant construction
        (``np.prod(self.frame_shape)``), not traced data."""
        if isinstance(a, ast.Constant):
            return True
        if isinstance(a, ast.Attribute):
            return _attr_root(a) is not None
        if isinstance(a, (ast.Tuple, ast.List)):
            return all(HostNumpyOpInScannedEnv._static_arg(e)
                       for e in a.elts)
        if isinstance(a, ast.UnaryOp):
            return HostNumpyOpInScannedEnv._static_arg(a.operand)
        return False

    def check(self, ctx: ModuleContext) -> list[Finding]:
        scanned = self._scanned_functions(ctx)
        out = []
        for fn in scanned:
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                sub = ctx.enclosing_function(node)
                # nested defs inside a scanned fn are scanned too; a
                # node inside some OTHER nested non-scanned def is not
                # reachable this way unless the closure marked it
                while sub is not None and sub is not fn:
                    if sub in scanned:
                        break
                    sub = ctx.enclosing_function(sub)
                f = node.func
                if (isinstance(f, ast.Attribute)
                        and _attr_root(f) in _NUMPY_ALIASES
                        and not all(self._static_arg(a)
                                    for a in node.args)):
                    out.append(ctx.finding(
                        self, node,
                        f"np.{f.attr}() in '{fn.name}', a lax.scan-scanned "
                        f"body — host numpy runs at trace time; use "
                        f"jnp.{f.attr} inside the compiled rollout"))
                elif (isinstance(f, ast.Name) and f.id in self._BUILTINS
                        and node.args
                        and not all(isinstance(a, ast.Constant)
                                    for a in node.args)):
                    out.append(ctx.finding(
                        self, node,
                        f"{f.id}() in '{fn.name}', a lax.scan-scanned "
                        f"body — pulls a traced value to host; keep it a "
                        f"traced array"))
                elif (isinstance(f, ast.Attribute) and f.attr == "item"
                        and not node.args):
                    out.append(ctx.finding(
                        self, node,
                        f".item() in '{fn.name}', a lax.scan-scanned "
                        f"body — pulls a traced value to host; keep it a "
                        f"traced array"))
        return out
