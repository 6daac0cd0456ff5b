"""Project-wide call graph for the interprocedural analyses.

The per-module :class:`~nbodykit_tpu.lint.scopes.ModuleContext` answers
"what does this name mean *here*"; this module stitches the contexts of
one lint run into a :class:`Project` that answers "what function does
this call actually reach", across modules, through the wrapper idioms
the codebase uses everywhere:

- ``fast = jax.jit(step, donate_argnums=(0,))`` — calling ``fast``
  calls ``step``, with argument 0 donated;
- ``prog = instrumented_jit(lambda v: ..., label=..., donate_argnums=0)``
  — the diagnostics drop-in, same semantics;
- ``@functools.lru_cache`` builders and ``functools.partial`` — the
  wrapper is transparent for call-graph purposes;
- ``raw, jitted = _slab_programs(...)`` then
  ``(jitted if eager else raw)(x)`` — the cached program pair: the
  names resolve through the builder's returned tuple, and the
  conditional pick reaches the one body both forms run;
- ``prog = _ell_program(...)`` then ``prog(x)`` — a builder whose one
  return is one wrapped body: the name resolves to that body;
- ``from ..parallel import dfft; dfft.rfftn_single_lowmem(box)`` —
  resolved through the import alias table to the def in the other
  module's context.

Resolution is deliberately conservative: a call that cannot be pinned
to exactly one def resolves to ``None`` and the analyses stay silent
about it.  As a pragmatic fallback, an unresolved dotted call whose
*tail* name matches exactly one module-level def project-wide resolves
to that def — this is what lets ``pm._plan.r2c(...)``-style calls and
package-``__init__`` re-exports participate without executing any
imports.  Everything here is stdlib-only, same as the rest of the
package.
"""

import ast
import collections

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)

# wrapper constructors that are call-transparent: calling the wrapper
# calls the (first) function argument
_JIT_WRAPPER_TAILS = frozenset({
    'jit', 'pjit', 'pmap', 'instrumented_jit'})
_TRANSPARENT_TAILS = frozenset({
    'partial', 'lru_cache', 'cache', 'shard_map', 'checkpoint',
    'remat', 'vmap'})

FuncRef = collections.namedtuple('FuncRef', ['ctx', 'node', 'module'])
# how a call site reaches a function: donate = frozenset of donated
# positional indices (from the jit wrapper construction, if any);
# jitted = the call goes through a jit-family wrapper
CallTarget = collections.namedtuple(
    'CallTarget', ['ref', 'donate', 'jitted'])


def module_name(canonical):
    """Dotted module name for a canonical repo-relative path
    (``nbodykit_tpu/parallel/dfft.py`` -> ``nbodykit_tpu.parallel.dfft``,
    ``bench.py`` -> ``bench``)."""
    p = canonical[:-3] if canonical.endswith('.py') else canonical
    parts = [s for s in p.replace('\\', '/').split('/') if s]
    if parts and parts[-1] == '__init__':
        parts = parts[:-1]
    return '.'.join(parts) or canonical


def _donate_positions(call):
    """Literal ``donate_argnums`` positions of a jit-family call."""
    out = set()
    for kw in call.keywords:
        if kw.arg != 'donate_argnums':
            continue
        vals = kw.value.elts if isinstance(
            kw.value, (ast.Tuple, ast.List)) else [kw.value]
        for v in vals:
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                out.add(v.value)
    return frozenset(out)


class Project(object):
    """All modules of one lint run, plus the derived call graph.

    Built once by :func:`~nbodykit_tpu.lint.walker.lint_paths` and
    shared by every interprocedural rule via ``ctx.project``; analyses
    cache their fixpoint summaries on the instance (``_coll_summaries``
    from collectives.py, ``_mem_summaries`` from sizes.py) so the
    project is walked once per rule family, not once per module.
    """

    def __init__(self, contexts, memory_config=None):
        self.contexts = list(contexts)
        self.memory_config = memory_config
        self.by_module = {}
        #: 'mod.func' -> FuncRef for module-level defs
        self.defs = {}
        #: bare function name -> [FuncRef] (module-level defs only)
        self.by_tail = collections.defaultdict(list)
        for ctx in self.contexts:
            mod = module_name(getattr(ctx, 'canonical', ctx.path))
            ctx.module = mod
            ctx.project = self
            self.by_module[mod] = ctx
            for name, fn in ctx.defs_by_scope.get(ctx.tree, {}).items():
                ref = FuncRef(ctx, fn, mod)
                self.defs['%s.%s' % (mod, name)] = ref
                self.by_tail[name].append(ref)
        # per-context wrapper tables are built lazily
        self._wrapper_cache = {}

    # -- wrapper tables ----------------------------------------------------

    def _wrappers(self, ctx):
        """{scope node: {name: (target expr or node, donate, jitted)}}
        for assignments like ``w = jax.jit(f, donate_argnums=...)``."""
        table = self._wrapper_cache.get(id(ctx))
        if table is not None:
            return table
        table = {}
        unpacks = []
        call_assigns = {}       # (scope, name) -> Call node
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Assign):
                continue
            scope = ctx.enclosing_scope(node)
            if isinstance(node.value, ast.Call):
                unwrapped = self._unwrap(ctx, node.value)
                if unwrapped is not None:
                    target, donate, jitted = unwrapped
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            table.setdefault(scope, {})[t.id] = \
                                (ctx, target, donate, jitted)
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        call_assigns[(scope, t.id)] = node.value
            if len(node.targets) == 1 and \
                    isinstance(node.targets[0], (ast.Tuple, ast.List)):
                unpacks.append((scope, node))
        # the simple entries are in place before the unpack pass may
        # re-enter this table through _resolve
        self._wrapper_cache[id(ctx)] = table
        for scope, node in unpacks:
            # tuple-unpack of a program-builder's return — the
            # lru_cache'd ``progs = _lowmem_programs(...)`` /
            # ``r0, r1, zeros, upd = progs`` idiom (dfft.py): map each
            # unpacked name to the corresponding element of the
            # builder's literal return tuple, resolved in the
            # BUILDER's context
            targets = node.targets[0]
            call = node.value
            if isinstance(call, ast.Name):
                # unpack of a name previously bound to a builder call
                for s in ctx.scope_chain(node):
                    hit = call_assigns.get((s, call.id))
                    if hit is not None:
                        call = hit
                        break
            if not isinstance(call, ast.Call):
                continue
            ref = self._builder_ref(ctx, call)
            if ref is None:
                continue
            ret = self._single_return(ref)
            if not isinstance(ret, (ast.Tuple, ast.List)) or \
                    len(ret.elts) != len(targets.elts):
                continue
            for t, elt in zip(targets.elts, ret.elts):
                if not isinstance(t, ast.Name):
                    continue
                ent = self._element_entry(ref, elt)
                if ent is not None:
                    table.setdefault(scope, {})[t.id] = ent
        for (scope, name), call in call_assigns.items():
            # ``prog = _ell_program(...)`` (convpower/fkp.py): a
            # builder whose one return is one wrapped body
            ref = self._builder_ref(ctx, call)
            ret = ref and self._single_return(ref)
            if isinstance(ret, ast.Call):
                ent = self._element_entry(ref, ret)
                if ent is not None:
                    table.setdefault(scope, {})[name] = ent
        return table

    def _builder_ref(self, ctx, call):
        """The def a builder call reaches, or None."""
        ref = self._resolve(ctx, call.func, call, frozenset(), False)[0]
        if ref is None:
            ref = self._dotted_ref(ctx, call.func)
        if ref is None or isinstance(ref.node, ast.Lambda):
            return None
        return ref

    def _single_return(self, ref):
        """The value of the one ``return`` of a def, or None."""
        ret = None
        for node in ast.walk(ref.node):
            if isinstance(node, ast.Return) and \
                    ref.ctx.enclosing_function(node) is ref.node:
                if ret is not None:
                    return None     # several returns: ambiguous
                ret = node.value
        return ret

    def _element_entry(self, ref, elt):
        """Wrapper-table entry for one element of a builder's return
        tuple, resolved in the builder's context."""
        bctx = ref.ctx
        if isinstance(elt, ast.Call):
            unwrapped = self._unwrap(bctx, elt)
            if unwrapped is None:
                return None
            # ``instrumented_jit(body, label=...)``: ``body`` is a
            # name of the builder's scope, not of the caller's, also
            # where both live in one module
            target, donate, jitted = unwrapped
            tref, donate, jitted = self._resolve(
                bctx, target, elt, donate, jitted)
            if tref is not None:
                return (bctx, tref.node, donate, jitted)
            return (bctx,) + unwrapped
        if isinstance(elt, (ast.Name, ast.Attribute)):
            tref, donate, jitted = self._resolve(
                bctx, elt, elt, frozenset(), False)
            if tref is not None:
                return (bctx, tref.node, donate, jitted)
        return None

    def _unwrap(self, ctx, call, depth=0):
        """Peel jit/partial/lru_cache/shard_map wrappers off a Call,
        returning (innermost function expr/node, donate, jitted) or
        None when the call is not a recognized wrapper."""
        if depth > 4 or not isinstance(call, ast.Call):
            return None
        q = ctx.call_name(call) or ''
        tail = q.rsplit('.', 1)[-1]
        if tail in _JIT_WRAPPER_TAILS:
            donate, jitted = _donate_positions(call), True
        elif tail in _TRANSPARENT_TAILS:
            donate, jitted = frozenset(), False
        elif isinstance(call.func, ast.Call):
            # lru_cache(maxsize=8)(f)
            fq = ctx.call_name(call.func) or ''
            if fq.rsplit('.', 1)[-1] in ('lru_cache', 'cache') \
                    and call.args:
                return (call.args[0], frozenset(), False)
            return None
        else:
            return None
        if not call.args:
            return None
        inner = call.args[0]
        if isinstance(inner, ast.Call):
            sub = self._unwrap(ctx, inner, depth + 1)
            if sub is not None:
                # donation is declared on the OUTERMOST jit
                t, d, j = sub
                return (t, donate or d, jitted or j)
            return (inner, donate, jitted)
        return (inner, donate, jitted)

    # -- resolution --------------------------------------------------------

    def resolve_name(self, ctx, node, at):
        """FuncRef for a Name/Attribute reference, or None.

        Order: local defs through the scope chain, wrapper
        assignments (returning the *wrapped* function), canonical
        dotted names against the project def table, then the
        unique-tail fallback.
        """
        ref, _, _ = self._resolve(ctx, node, at, frozenset(), False)
        if ref is None:
            ref = self._dotted_ref(ctx, node)
        return ref

    def resolve_call(self, ctx, call):
        """CallTarget for a Call node (or None): the def ultimately
        executed, the donated positions, and whether a jit wrapper is
        in between."""
        if not isinstance(call, ast.Call):
            return None
        return self._call_target(ctx, call.func, call)

    def _call_target(self, ctx, func, call):
        """CallTarget of calling the expression ``func`` at ``call``."""
        # the pick between the two forms of one cached program,
        # ``(jitted if eager else raw)(x)``: both arms run one body,
        # so the call reaches it whichever is taken; arms that reach
        # different defs stay unresolved
        if isinstance(func, ast.IfExp):
            a = self._call_target(ctx, func.body, call)
            b = self._call_target(ctx, func.orelse, call)
            if a is None or b is None or a.ref is None or \
                    b.ref is None or a.ref.node is not b.ref.node:
                return None
            return CallTarget(a.ref, a.donate & b.donate,
                              a.jitted and b.jitted)
        # immediate form: jax.jit(f, donate_argnums=..)(x)
        if isinstance(func, ast.Call):
            unwrapped = self._unwrap(ctx, func)
            if unwrapped is not None:
                target, donate, jitted = unwrapped
                ref = self._ref_of(ctx, target, call)
                return CallTarget(ref, donate, jitted)
        ref, donate, jitted = self._resolve(
            ctx, func, call, frozenset(), False)
        if ref is None and donate == frozenset() and not jitted:
            # dotted / unique-tail fallback
            ref = self._dotted_ref(ctx, func)
            if ref is None:
                return None
            return CallTarget(ref, frozenset(), False)
        return CallTarget(ref, donate, jitted)

    def _resolve(self, ctx, node, at, donate, jitted, depth=0):
        """(FuncRef or None, donate, jitted) following local wrapper
        assignments."""
        if depth > 4:
            return None, donate, jitted
        if isinstance(node, _FUNC_NODES):
            return FuncRef(ctx, node, getattr(ctx, 'module', '?')), \
                donate, jitted
        if isinstance(node, ast.Name):
            wrappers = self._wrappers(ctx)
            for scope in ctx.scope_chain(at):
                ent = wrappers.get(scope, {}).get(node.id)
                if ent is not None:
                    ectx, target, d, j = ent
                    return self._resolve(
                        ectx, target,
                        at if ectx is ctx else target,
                        donate or d, jitted or j, depth + 1)
                fn = ctx.defs_by_scope.get(scope, {}).get(node.id)
                if fn is not None:
                    ref = FuncRef(ctx, fn, getattr(ctx, 'module', '?'))
                    # decorator-declared donation on the def itself
                    d2, j2 = self._decorated(ctx, fn)
                    return ref, donate or d2, jitted or j2
        if isinstance(node, (ast.Name, ast.Attribute)):
            ref = self._dotted_ref(ctx, node)
            if ref is not None:
                d2, j2 = self._decorated(ref.ctx, ref.node)
                return ref, donate or d2, jitted or j2
        return None, donate, jitted

    def _ref_of(self, ctx, target, at):
        ref, _, _ = self._resolve(ctx, target, at, frozenset(), False)
        return ref

    def _decorated(self, ctx, fn):
        """(donate, jitted) declared by jit-family decorators on a
        def."""
        for dec in getattr(fn, 'decorator_list', ()):
            target = dec.func if isinstance(dec, ast.Call) else dec
            q = ctx.qual(target) or ''
            if q.rsplit('.', 1)[-1] in _JIT_WRAPPER_TAILS:
                donate = _donate_positions(dec) \
                    if isinstance(dec, ast.Call) else frozenset()
                return donate, True
        return frozenset(), False

    def _dotted_ref(self, ctx, node):
        """Cross-module resolution: canonical dotted name against the
        project def table, else the unique-tail fallback."""
        q = ctx.qual(node)
        if q is None:
            # phase_fns['paint'](...) and friends: a Subscript with a
            # constant string key resolves by that key's tail
            if isinstance(node, ast.Subscript) and \
                    isinstance(node.slice, ast.Constant) and \
                    isinstance(node.slice.value, str):
                q = node.slice.value
            else:
                return None
        ref = self.defs.get(q)
        if ref is not None:
            return ref
        tail = q.rsplit('.', 1)[-1]
        cands = self.by_tail.get(tail, ())
        if len(cands) == 1:
            return cands[0]
        return None

    # -- iteration ---------------------------------------------------------

    def functions(self):
        """Every (ctx, function node) in the project, lambdas
        included, deterministic order."""
        for ctx in self.contexts:
            for fn in ctx.functions:
                yield ctx, fn

    def calls_in(self, ctx, fn):
        """Call nodes directly inside ``fn`` (not in nested defs)."""
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    ctx.enclosing_function(node) is fn:
                yield node


def single_project(ctx, memory_config=None):
    """A one-module Project for the single-file ``lint_source`` path
    (fixtures, editor integrations); attaches itself to ``ctx``."""
    ctx.canonical = getattr(ctx, 'canonical', ctx.path)
    return Project([ctx], memory_config=memory_config)
