"""NBK7xx — interprocedural precision-flow analysis.

The mixed-precision direction (ROADMAP #5 — bf16 mesh replicas,
compressed a2a payloads) makes precision a *budgeted* quantity: the
aliasing/mass-assignment error-budget papers set how much drift P(k)
may accumulate, and every silent demotion spends budget nobody
accounted for.  The runtime cannot catch these cheaply — a bf16
all_to_all result consumed as-is produces numbers that are merely
*slightly* wrong.  This pass proves where the budget is spent,
statically.

**The dtype lattice.**  Values carry a canonical dtype fact —
``float64 > float32 > bfloat16/float16`` and the int width family
``int64 > int32 > int16 > int8`` — joined over assignments; a name
assigned conflicting dtypes degrades to unknown, and unknown facts
keep every rule silent (same conservatism as the NBK5xx size model).
Facts come from dtype tokens (``'f4'``/``jnp.bfloat16``/project
constants), ``astype``/``asarray`` casts, allocator ``dtype=``
arguments, ``preferred_element_type``, and — interprocedurally — from
return summaries run to fixpoint over the
:class:`~nbodykit_tpu.lint.callgraph.Project` graph, with
parameter-passthrough mapping so a helper returning its argument
propagates the argument's dtype, not a guess.

Rules
-----
NBK701  collective payload narrowed to bf16/f16 whose *result* is
        consumed without re-widening — the compressed-collective
        contract is bf16-in/f32-out; keeping the result narrow
        silently propagates the demotion downstream.
NBK702  accumulation (``+=`` / self-add in a loop / ``.at[].add``)
        into a bf16/f16 accumulator without a compensated-sum
        (two-sum hi/lo split) idiom in the same function — bf16 has 8
        mantissa bits; plain accumulation loses mass.
NBK703  mixed-dtype arithmetic whose narrow side is mesh-sized — the
        promotion materializes a full-mesh copy at the wider dtype,
        defeating the reason the mesh was narrow.
NBK704  the int32 flattened-index rule (NBK302) upgraded with value
        ranges: factor bounds from literals, module/project constants
        and the declared ``--nmesh`` config prove an index chain safe
        (< 2**31, silent), prove it overflowing (>= 2**31, definite
        finding), or leave it unbounded (finding, unless the function
        carries a trace-time ``iinfo(int32)`` guard — the audited
        paint.py pattern, which this rule recognizes and NBK302
        cannot).
"""

import ast
import collections

from . import sizes as _sizes

# -- the lattice -------------------------------------------------------------

#: canonical float ids -> width rank (bf16 and f16 share the bottom)
FLOAT_WIDTH = {'float64': 3, 'float32': 2, 'bfloat16': 1,
               'float16': 1}
INT_WIDTH = {'int64': 3, 'int32': 2, 'int16': 1, 'int8': 0,
             'uint64': 3, 'uint32': 2, 'uint16': 1, 'uint8': 0}
COMPLEX_WIDTH = {'complex128': 3, 'complex64': 2}

NARROW_FLOATS = frozenset({'bfloat16', 'float16'})

#: dtype string spellings -> canonical id (numpy letter codes: i8 is
#: the 8-BYTE int64, f8 is float64)
_STRING_TOKENS = {
    'float64': 'float64', 'f8': 'float64', '<f8': 'float64',
    '>f8': 'float64', '=f8': 'float64', 'double': 'float64',
    'd': 'float64',
    'float32': 'float32', 'f4': 'float32', '<f4': 'float32',
    '>f4': 'float32', '=f4': 'float32', 'single': 'float32',
    'bfloat16': 'bfloat16', 'bf16': 'bfloat16',
    'float16': 'float16', 'f2': 'float16', 'half': 'float16',
    'int64': 'int64', 'i8': 'int64', '<i8': 'int64', '>i8': 'int64',
    '=i8': 'int64',
    'int32': 'int32', 'i4': 'int32', '<i4': 'int32', '>i4': 'int32',
    '=i4': 'int32',
    'int16': 'int16', 'i2': 'int16', 'int8': 'int8', 'i1': 'int8',
    'uint64': 'uint64', 'u8': 'uint64', 'uint32': 'uint32',
    'u4': 'uint32', 'uint16': 'uint16', 'u2': 'uint16',
    'uint8': 'uint8', 'u1': 'uint8',
    'complex128': 'complex128', 'c16': 'complex128',
    'complex64': 'complex64', 'c8': 'complex64',
}

#: numpy/jnp attribute tails -> canonical id
_ATTR_TOKENS = {
    'float64': 'float64', 'double': 'float64',
    'float32': 'float32', 'single': 'float32',
    'bfloat16': 'bfloat16', 'float16': 'float16', 'half': 'float16',
    'int64': 'int64', 'int32': 'int32', 'int16': 'int16',
    'int8': 'int8', 'uint64': 'uint64', 'uint32': 'uint32',
    'uint16': 'uint16', 'uint8': 'uint8',
    'complex128': 'complex128', 'complex64': 'complex64',
}

#: call tails whose result keeps the dtype of their array operand
_PRESERVE_TAILS = frozenset({
    'transpose', 'reshape', 'ravel', 'flatten', 'broadcast_to',
    'concatenate', 'stack', 'hstack', 'vstack', 'pad', 'roll',
    'flip', 'squeeze', 'expand_dims', 'copy', 'negative',
    'dynamic_slice', 'dynamic_update_slice', 'take',
    'take_along_axis', 'sum', 'max', 'min', 'prod', 'cumsum',
    'sort', 'fft_chunked', 'mod', 'clip', 'abs',
})

#: collectives carrying an array payload in args[0]
_PAYLOAD_COLLECTIVES = frozenset({
    'psum', 'pmean', 'pmax', 'pmin', 'ppermute', 'pshuffle',
    'all_gather', 'all_to_all', 'psum_scatter', 'pbroadcast'})

_VARIED = '<varied>'


def dtype_token(ctx, node):
    """Canonical dtype id of a dtype-token expression, or None:
    string literals (through module/project constants) and
    ``numpy.float32``/``jnp.bfloat16``-style attributes."""
    if node is None:
        return None
    s = ctx.const_str(node)
    if s is not None:
        return _STRING_TOKENS.get(s)
    q = ctx.qual(node)
    if q is None:
        return None
    head, _, tail = q.rpartition('.')
    if tail in _ATTR_TOKENS and (
            head in ('numpy', 'jax.numpy') or head.endswith('numpy')):
        return _ATTR_TOKENS[tail]
    return None


def promote(a, b):
    """Joint dtype of a binary op, or None when unknown.  Same family
    -> the wider member; float x int -> the float; complex absorbs
    floats."""
    if a is None or b is None:
        return None
    for fam in (COMPLEX_WIDTH, FLOAT_WIDTH, INT_WIDTH):
        if a in fam and b in fam:
            return a if fam[a] >= fam[b] else b
    for wide, narrow in ((COMPLEX_WIDTH, FLOAT_WIDTH),
                        (COMPLEX_WIDTH, INT_WIDTH),
                        (FLOAT_WIDTH, INT_WIDTH)):
        if a in wide and b in narrow:
            return a
        if b in wide and a in narrow:
            return b
    return None


def _weak_int(expr):
    """A bare int literal (possibly negated) — weakly typed in jax:
    it adopts the other operand's dtype instead of promoting."""
    if isinstance(expr, ast.UnaryOp):
        expr = expr.operand
    if isinstance(expr, ast.Constant):
        return isinstance(expr.value, int) and \
            not isinstance(expr.value, bool)
    if isinstance(expr, ast.BinOp):
        return _weak_int(expr.left) and _weak_int(expr.right)
    return False


def _scalarish(expr):
    """Arithmetic over names and int literals only (``s // 2 - 1``)
    — the shape of a Python scalar-int expression, as opposed to an
    array expression (calls, subscripts, attributes)."""
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Constant):
            if not isinstance(sub.value, int) or \
                    isinstance(sub.value, bool):
                return False
        elif not isinstance(sub, (ast.Name, ast.BinOp, ast.UnaryOp,
                                  ast.operator, ast.unaryop,
                                  ast.expr_context)):
            return False
    return True


DtypeSummary = collections.namedtuple(
    'DtypeSummary', ['returns', 'ret_params'])


class _FuncDtype(object):
    """Per-function dtype facts: name -> canonical id (or _VARIED
    when assignments conflict; absent = unknown)."""

    def __init__(self, analysis, ctx, fn):
        self.analysis = analysis
        self.ctx = ctx
        self.fn = fn
        a = fn.args
        self.params = [p.arg for p in
                       a.posonlyargs + a.args + a.kwonlyargs
                       if p.arg != 'self']
        self.labels = {}
        self._infer()

    def _infer(self):
        ctx, fn = self.ctx, self.fn
        for _ in range(3):
            changed = False
            for node in ast.walk(fn):
                if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                    continue
                if ctx.enclosing_function(node) is not fn:
                    continue
                if node.value is None:
                    continue
                d = self.expr_dtype(node.value)
                if d is None:
                    continue
                targets = node.targets \
                    if isinstance(node, ast.Assign) else [node.target]
                for t in targets:
                    if isinstance(t, ast.Tuple) and \
                            isinstance(d, tuple) and \
                            len(t.elts) == len(d):
                        # idx, w = window_weights(...) unpack
                        for elt, de in zip(t.elts, d):
                            if isinstance(elt, ast.Name) and \
                                    de is not None:
                                changed |= self._join(elt.id, de)
                        continue
                    if not isinstance(t, ast.Name):
                        continue
                    changed |= self._join(t.id, d)
            if not changed:
                break

    def _join(self, name, d):
        old = self.labels.get(name)
        new = d if old in (None, d) else _VARIED
        if new != old:
            self.labels[name] = new
            return True
        return False

    def name_dtype(self, name):
        d = self.labels.get(name)
        return None if d == _VARIED else d

    def expr_dtype(self, expr):
        """Canonical dtype id of an expression (or a tuple of them
        for tuple expressions), or None (unknown)."""
        if expr is None:
            return None
        if isinstance(expr, ast.Name):
            return self.name_dtype(expr.id)
        if isinstance(expr, ast.Call):
            return self.call_dtype(expr)
        if isinstance(expr, ast.BinOp):
            dl = self.expr_dtype(expr.left)
            dr = self.expr_dtype(expr.right)
            # a bare int literal is weakly typed: it adopts the
            # array side's dtype (idx - (s // 2 - 1) stays int32)
            if dl is None and dr is not None and \
                    _weak_int(expr.left):
                return dr if not isinstance(dr, tuple) else None
            if dr is None and dl is not None and \
                    _weak_int(expr.right):
                return dl if not isinstance(dl, tuple) else None
            # int-array op scalar-ish int expression (idx - (s//2-1)):
            # a Python scalar int never widens an int array under jax
            # weak typing.  Int family only — an unknown float side
            # would genuinely promote.
            if dl in INT_WIDTH and dr is None and \
                    _scalarish(expr.right):
                return dl
            if dr in INT_WIDTH and dl is None and \
                    _scalarish(expr.left):
                return dr
            if isinstance(dl, tuple) or isinstance(dr, tuple):
                return None
            return promote(dl, dr)
        if isinstance(expr, ast.UnaryOp):
            return self.expr_dtype(expr.operand)
        if isinstance(expr, ast.IfExp):
            a = self.expr_dtype(expr.body)
            return a if a == self.expr_dtype(expr.orelse) else None
        if isinstance(expr, ast.Tuple):
            ds = tuple(self.expr_dtype(e) for e in expr.elts)
            return ds if any(d is not None for d in ds) else None
        if isinstance(expr, ast.Subscript):
            d = self.expr_dtype(expr.value)
            if isinstance(d, tuple):
                s = expr.slice
                if isinstance(s, ast.Constant) and \
                        isinstance(s.value, int) and \
                        0 <= s.value < len(d):
                    return d[s.value]
                return None
            return d
        if isinstance(expr, ast.Attribute):
            if expr.attr in ('T', 'mT'):
                return self.expr_dtype(expr.value)
            if isinstance(expr.value, ast.Name) and \
                    expr.value.id == 'self':
                return self.analysis.self_attr_dtype(
                    self.ctx, self.fn, expr.attr)
            return None
        return None

    def call_dtype(self, call):
        ctx = self.ctx
        tail = _sizes._call_tail(ctx, call)
        if tail is None and isinstance(call.func, ast.Attribute):
            # method on a call result (jnp.floor(x).astype(...)):
            # no resolvable qual, but the attr name is the tail
            tail = call.func.attr
        dtype_kw = None
        for kw in call.keywords:
            if kw.arg == 'dtype':
                dtype_kw = dtype_token(ctx, kw.value)
            elif kw.arg == 'preferred_element_type':
                t = dtype_token(ctx, kw.value)
                if t is not None:
                    return t
        if tail == 'astype':
            if call.args:
                return dtype_token(ctx, call.args[0]) or dtype_kw
            return dtype_kw
        if tail in ('asarray', 'array'):
            if dtype_kw is not None:
                return dtype_kw
            if len(call.args) >= 2:
                t = dtype_token(ctx, call.args[1])
                if t is not None:
                    return t
            return self.expr_dtype(call.args[0]) if call.args else None
        if tail in _sizes.ALLOC_TAILS or tail in ('arange', 'linspace',
                                                  'one_hot', 'eye'):
            if dtype_kw is not None:
                return dtype_kw
            # jnp.zeros(shape, jnp.bfloat16) positional dtype
            for a in call.args[1:]:
                t = dtype_token(ctx, a)
                if t is not None:
                    return t
            return None
        if tail in _sizes.ALLOC_LIKE_TAILS:
            if dtype_kw is not None:
                return dtype_kw
            return self.expr_dtype(call.args[0]) if call.args else None
        if tail in _PAYLOAD_COLLECTIVES:
            return self.expr_dtype(call.args[0]) if call.args else None
        if tail == 'where' and len(call.args) == 3:
            da = self.expr_dtype(call.args[1])
            db = self.expr_dtype(call.args[2])
            if da is None and db is not None and \
                    _weak_int(call.args[1]):
                return db if not isinstance(db, tuple) else None
            if db is None and da is not None and \
                    _weak_int(call.args[2]):
                return da if not isinstance(da, tuple) else None
            if isinstance(da, tuple) or isinstance(db, tuple):
                return None
            return promote(da, db)
        if tail in _ATTR_TOKENS:
            # jnp.float32(x)-style cast constructor
            q = ctx.call_name(call) or ''
            head = q.rpartition('.')[0]
            if head in ('numpy', 'jax.numpy') or \
                    head.endswith('numpy'):
                return _ATTR_TOKENS[tail]
        if tail in _PRESERVE_TAILS:
            # x.reshape(...) preserves x; jnp.reshape(x, ...)
            # preserves args[0] (func.value is the module there)
            if isinstance(call.func, ast.Attribute):
                d = self.expr_dtype(call.func.value)
                if d is not None and not isinstance(d, tuple):
                    return d
            return self.expr_dtype(call.args[0]) if call.args else None
        # interprocedural: resolved callee summary with parameter
        # passthrough
        project = getattr(ctx, 'project', None)
        if project is not None:
            tgt = project.resolve_call(ctx, call)
            if tgt is not None and tgt.ref is not None and \
                    tgt.ref.node is not self.fn:
                summ = self.analysis.summary_of(tgt.ref.node)
                if summ.returns is not None:
                    return summ.returns
                if summ.ret_params:
                    ds = {d for d in self._mapped_arg_dtypes(
                        call, tgt.ref.node, summ.ret_params)}
                    if len(ds) == 1:
                        return ds.pop()
        return None

    def _mapped_arg_dtypes(self, call, callee, ret_params):
        a = callee.args
        names = [p.arg for p in a.posonlyargs + a.args]
        offset = 1 if names and names[0] == 'self' else 0
        for i, arg in enumerate(call.args):
            pos = i + offset
            if pos < len(names) and names[pos] in ret_params:
                yield self.expr_dtype(arg)
        for kw in call.keywords:
            if kw.arg in ret_params:
                yield self.expr_dtype(kw.value)

    def returns_kind(self):
        """(returns dtype or None, frozenset of passthrough param
        names)."""
        fn = self.fn
        if isinstance(fn, ast.Lambda):
            exprs = [fn.body]
        else:
            exprs = [n.value for n in ast.walk(fn)
                     if isinstance(n, ast.Return) and
                     n.value is not None and
                     self.ctx.enclosing_function(n) is fn]
        dtypes = set()
        passthrough = set()
        for e in exprs:
            if isinstance(e, ast.Name) and e.id in self.params and \
                    e.id not in self.labels:
                passthrough.add(e.id)
                continue
            dtypes.add(self.expr_dtype(e))
        if passthrough and not dtypes:
            return None, frozenset(passthrough)
        if len(dtypes) == 1 and not passthrough:
            return dtypes.pop(), frozenset()
        return None, frozenset()


class _Analysis(object):
    """Project-wide fixpoint of DtypeSummary per function, plus
    instance-attribute facts (``self.ncell = jnp.asarray(_, int32)``
    in one method proves ``self.ncell`` int32 in the others)."""

    def __init__(self, project):
        self.project = project
        self.summaries = {}
        self._func_dtype = {}
        self._class_attrs = {}
        for _ in range(4):
            changed = False
            for ctx, fn in project.functions():
                fd = _FuncDtype(self, ctx, fn)
                returns, ret_params = fd.returns_kind()
                summ = DtypeSummary(returns, ret_params)
                if summ != self.summaries.get(id(fn)):
                    self.summaries[id(fn)] = summ
                    changed = True
                self._func_dtype[id(fn)] = fd
                changed |= self._harvest_attrs(ctx, fn, fd)
            if not changed:
                break

    def _harvest_attrs(self, ctx, fn, fd):
        cls = _enclosing_class(ctx, fn)
        if cls is None:
            return False
        table = self._class_attrs.setdefault(id(cls), {})
        changed = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign) or \
                    ctx.enclosing_function(node) is not fn:
                continue
            d = fd.expr_dtype(node.value)
            if d is None or isinstance(d, tuple):
                continue
            for t in node.targets:
                if isinstance(t, ast.Attribute) and \
                        isinstance(t.value, ast.Name) and \
                        t.value.id == 'self':
                    old = table.get(t.attr)
                    new = d if old in (None, d) else _VARIED
                    if new != old:
                        table[t.attr] = new
                        changed = True
        return changed

    def self_attr_dtype(self, ctx, fn, attr):
        cls = _enclosing_class(ctx, fn)
        if cls is None:
            return None
        d = self._class_attrs.get(id(cls), {}).get(attr)
        return None if d == _VARIED else d

    def summary_of(self, fn):
        return self.summaries.get(
            id(fn), DtypeSummary(None, frozenset()))

    def func_dtype(self, fn):
        return self._func_dtype.get(id(fn))


def _enclosing_class(ctx, fn):
    """The ClassDef a method belongs to, or None (climbs parents —
    ClassDef is not a scope node, so scope_chain skips it)."""
    n = ctx.parents.get(fn)
    while n is not None:
        if isinstance(n, ast.ClassDef):
            return n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Module)):
            return None
        n = ctx.parents.get(n)
    return None


def analysis_for(project):
    cached = getattr(project, '_dtype_analysis', None)
    if cached is None:
        cached = _Analysis(project)
        project._dtype_analysis = cached
    return cached


def _project_of(ctx):
    project = getattr(ctx, 'project', None)
    if project is None:
        from .callgraph import single_project
        project = single_project(ctx)
    return project


# ---------------------------------------------------------------------------
# rule entry points (wrapped into Findings by rules.py)


def find_demoted_collectives(ctx):
    """NBK701 raw findings: (call, dtype) — collective with a narrow
    float payload whose result is not immediately re-widened."""
    project = _project_of(ctx)
    an = analysis_for(project)
    out = []
    for fn in ctx.functions:
        fd = an.func_dtype(fn)
        if fd is None:
            continue
        for call in project.calls_in(ctx, fn):
            if not ctx.is_collective(call) or not call.args:
                continue
            q = ctx.call_name(call) or ''
            if q.rsplit('.', 1)[-1] not in _PAYLOAD_COLLECTIVES:
                continue
            d = fd.expr_dtype(call.args[0])
            if d not in NARROW_FLOATS:
                continue
            if _rewidened(ctx, call):
                continue        # the bf16-in/f32-out contract: fine
            out.append((call, d))
    return out


def _rewidened(ctx, call):
    """Is the collective's result immediately .astype()-cast to a
    float at least as wide as f32?"""
    parent = ctx.parents.get(call)
    if isinstance(parent, ast.Attribute) and parent.attr == 'astype':
        gp = ctx.parents.get(parent)
        if isinstance(gp, ast.Call) and gp.func is parent and gp.args:
            t = dtype_token(ctx, gp.args[0])
            return t is not None and FLOAT_WIDTH.get(t, 0) >= 2
    return False


def find_uncompensated_accumulations(ctx):
    """NBK702 raw findings: (node, name, dtype) — accumulation into a
    definitely-narrow accumulator in a function with no two-sum
    (hi/lo residual) idiom."""
    project = _project_of(ctx)
    an = analysis_for(project)
    out = []
    for fn in ctx.functions:
        fd = an.func_dtype(fn)
        if fd is None or _has_compensated_idiom(ctx, fn):
            continue
        for node in ast.walk(fn):
            if ctx.enclosing_function(node) is not fn:
                continue
            name = _accumulator_name(ctx, node)
            if name is None:
                continue
            d = fd.name_dtype(name)
            if d in NARROW_FLOATS:
                out.append((node, name, d))
    return out


def _accumulator_name(ctx, node):
    """The accumulator a statement adds into, or None: ``acc += x``,
    loop-carried ``acc = acc + x``, ``mesh.at[idx].add(v)``."""
    if isinstance(node, ast.AugAssign) and \
            isinstance(node.op, (ast.Add, ast.Sub)) and \
            isinstance(node.target, ast.Name):
        return node.target.id
    if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
            isinstance(node.targets[0], ast.Name) and \
            isinstance(node.value, ast.BinOp) and \
            isinstance(node.value.op, (ast.Add, ast.Sub)):
        name = node.targets[0].id
        if ctx.in_loop(node, stop_at_function=True) and any(
                isinstance(s, ast.Name) and s.id == name
                for s in ast.walk(node.value)):
            return name
    if isinstance(node, ast.Call) and \
            isinstance(node.func, ast.Attribute) and \
            node.func.attr == 'add':
        base = node.func.value
        if isinstance(base, ast.Subscript) and \
                isinstance(base.value, ast.Attribute) and \
                base.value.attr == 'at' and \
                isinstance(base.value.value, ast.Name):
            return base.value.value.id
    return None


def _has_compensated_idiom(ctx, fn):
    """Does the function carry a two-sum residual split — an
    assignment whose value subtracts a value's own ``astype`` re-cast
    (the ``lo = (w - hi.astype(f32))`` shape, ops/histogram.py)?"""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign):
            continue
        for sub in ast.walk(node.value):
            if isinstance(sub, ast.BinOp) and \
                    isinstance(sub.op, ast.Sub):
                for side in (sub.left, sub.right):
                    for c in ast.walk(side):
                        if isinstance(c, ast.Call) and \
                                isinstance(c.func, ast.Attribute) and \
                                c.func.attr == 'astype':
                            return True
    return False


def find_promoting_mixed_arith(ctx):
    """NBK703 raw findings: (node, narrow, wide) — arithmetic whose
    mesh-sized operand is strictly narrower than the other side, so
    the promotion materializes a full-mesh copy at the wide dtype."""
    project = _project_of(ctx)
    an = analysis_for(project)
    mem = _sizes.analysis_for(project)
    out = []
    for fn in ctx.functions:
        fd = an.func_dtype(fn)
        fm = mem.func_mem(fn)
        if fd is None or fm is None:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.BinOp) or not isinstance(
                    node.op, (ast.Add, ast.Sub, ast.Mult, ast.Div)):
                continue
            if ctx.enclosing_function(node) is not fn:
                continue
            dl = fd.expr_dtype(node.left)
            dr = fd.expr_dtype(node.right)
            if dl not in FLOAT_WIDTH or dr not in FLOAT_WIDTH or \
                    FLOAT_WIDTH[dl] == FLOAT_WIDTH[dr]:
                continue
            narrow_expr, narrow, wide = (node.left, dl, dr) \
                if FLOAT_WIDTH[dl] < FLOAT_WIDTH[dr] \
                else (node.right, dr, dl)
            if _sizes._OWN not in fm.expr_labels(narrow_expr):
                continue
            out.append((node, narrow, wide))
    return out


# ---------------------------------------------------------------------------
# NBK704: the value-range upgrade of NBK302


_I32_STRINGS = frozenset({'i4', 'int32', '<i4', '>i4', '=i4'})
_I32_ATTRS = frozenset({'numpy.int32', 'jax.numpy.int32'})

_I32_MAX = 2 ** 31


def _mentions_i32(ctx, node):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and \
                isinstance(sub.value, str) and \
                sub.value in _I32_STRINGS:
            return True
        if ctx.qual(sub) in _I32_ATTRS:
            return True
    return False


def _chained_mult(node):
    if not (isinstance(node, ast.BinOp) and
            isinstance(node.op, ast.Mult)):
        return False
    for side in (node.left, node.right):
        for sub in ast.walk(side):
            if isinstance(sub, ast.BinOp) and \
                    isinstance(sub.op, (ast.Mult, ast.Add)):
                return True
    return False


def int_bound(ctx, node, config=None):
    """Static upper bound of an integer expression, or None: literal
    ints, module/project int constants, mesh-token names under a
    declared ``--nmesh`` config, and +|*|-|// arithmetic over
    those."""
    if isinstance(node, ast.Constant) and \
            isinstance(node.value, int) and \
            not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.BinOp):
        left = int_bound(ctx, node.left, config)
        right = int_bound(ctx, node.right, config)
        if isinstance(node.op, ast.Mult):
            if left is not None and right is not None:
                return left * right
        elif isinstance(node.op, ast.Add):
            if left is not None and right is not None:
                return left + right
        elif isinstance(node.op, ast.Sub):
            return left        # a - b <= a for non-negative b
        elif isinstance(node.op, ast.FloorDiv):
            if left is not None and right:
                return left // right
        elif isinstance(node.op, ast.Pow):
            if left is not None and right is not None:
                return left ** right
        return None
    if isinstance(node, ast.UnaryOp) and \
            isinstance(node.op, ast.USub):
        return 0        # negated term cannot push the bound up
    q = ctx.qual(node)
    if q is not None:
        tail = q.rsplit('.', 1)[-1]
        v = ctx.constants.get(tail)
        if isinstance(v, int) and not isinstance(v, bool):
            return v
        v = ctx.project_constants.get(tail)
        if isinstance(v, int) and not isinstance(v, bool):
            return v
        if config is not None and (
                _sizes._MESH_TOKEN_RE.match(tail) or
                _sizes._AXIS_NAME_RE.match(tail)):
            return config.nmesh
    return None


def _has_i32_guard(ctx, fn):
    """Does the function raise behind an ``iinfo(int32)``-style bound
    check before using the flat index — the paint.py trace-time
    guard?"""
    for node in ast.walk(fn):
        if not isinstance(node, ast.If):
            continue
        if ctx.enclosing_function(node) is not fn:
            continue
        dump = ast.dump(node.test)
        if 'iinfo' not in dump and '2147483647' not in dump and \
                str(_I32_MAX) not in dump:
            continue
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Raise):
                    return True
    return False


def _chain_is_i32(ctx, fd, stmt_value, sub):
    """Is this chained mult int32-typed?  Either the statement
    mentions i32 lexically (the NBK302 gate) or — the interprocedural
    upgrade — some operand of the chain carries a proven int32 fact
    from the dtype lattice (``i1`` unpacked from window_weights,
    ``self.ncell`` assigned in __init__)."""
    if _mentions_i32(ctx, stmt_value):
        return True
    if fd is None:
        return False
    for op in _operands(sub):
        if fd.expr_dtype(op) == 'int32':
            return True
    return False


def _operands(node):
    """The maximal non-arithmetic subexpressions of a chain — the
    level at which dtype facts apply (descending into a call would
    read facts from *before* an ``.astype`` changed them)."""
    if isinstance(node, ast.BinOp):
        for side in (node.left, node.right):
            for op in _operands(side):
                yield op
    elif isinstance(node, ast.UnaryOp):
        for op in _operands(node.operand):
            yield op
    else:
        yield node


def find_i32_range_overflow(ctx):
    """NBK704 raw findings: (node, verdict, bound) — chained int32
    index arithmetic judged by static value ranges.  verdict is
    'overflow' (bound >= 2**31: definite) or 'unbounded' (no bound
    derivable and no trace-time guard); provably-safe and guarded
    chains are silent."""
    project = _project_of(ctx)
    an = analysis_for(project)
    config = getattr(project, 'memory_config', None)
    out = []
    guarded_cache = {}
    reported = set()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.Assign, ast.Return, ast.Expr,
                                 ast.AugAssign, ast.AnnAssign)):
            continue
        value = getattr(node, 'value', None)
        if value is None:
            continue
        fn = ctx.enclosing_function(node)
        fd = an.func_dtype(fn) if fn is not None else None
        for sub in ast.walk(value):
            if not _chained_mult(sub) or id(sub) in reported:
                continue
            reported.add(id(sub))
            if not _chain_is_i32(ctx, fd, value, sub):
                continue
            bound = int_bound(ctx, sub, config)
            if bound is not None and bound < _I32_MAX:
                break       # proven safe: the upgrade over NBK302
            if bound is not None:
                out.append((sub, 'overflow', bound))
                break
            if fn is not None:
                if id(fn) not in guarded_cache:
                    guarded_cache[id(fn)] = _has_i32_guard(ctx, fn)
                if guarded_cache[id(fn)]:
                    break   # trace-time raise bounds it: audited safe
            out.append((sub, 'unbounded', None))
            break
    return out
