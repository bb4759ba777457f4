"""AST-based dygraph-to-static translation.

Reference parity: fluid/dygraph/dygraph_to_static/ (24 files —
IfElseTransformer, LoopTransformer, program_translator.py:680). TPU-native
design: instead of rewriting to fluid control-flow OPS, the transforms
rewrite Python `if`/`while` statements over Tensors into `_jst.cond` /
`_jst.while_loop` calls that dispatch at RUNTIME — plain Python control
flow when the predicate is concrete, `lax.cond`/`lax.while_loop` when it
is a traced value — so one converted function works eagerly AND under
jax.jit/jax.export with data-dependent branching.

Transform pipeline (each a reference transformer's TPU counterpart):
  1. _ForToWhileTransformer — `for i in range(...)` / `for x in tensor`
     become while loops (loop_transformer.py), increment-first so
     continue-guards cannot skip it;
  2. _EarlyExitTransformer — `break`/`continue` become guard flags and
     loop `return`s a single-exit flag+value pair
     (break_continue_transformer.py, return_transformer.py), leaving
     loops escape-free;
  3. _LogicalTransformer — and/or/not become runtime __jst_* calls that
     stay correct on traced booleans (logical_transformer.py);
  4. _ControlFlowTransformer — if/while become __jst_cond/__jst_while
     runtime-dispatch calls (lax.cond / lax.while_loop when traced).
Caveat: `return` inside a loop whose trip count is TRACED would need a
pre-known return structure for the lax carry; with concrete (trace-time)
bounds — the common dygraph pattern — it stages fine.
"""
from __future__ import annotations

import ast
import functools
import inspect
import textwrap


class _Undefined:
    """Placeholder for names assigned only inside a branch/loop body
    (dygraph_to_static's UndefinedVar)."""

    _inst = None

    def __new__(cls):
        if cls._inst is None:
            cls._inst = super().__new__(cls)
        return cls._inst

    def __repr__(self):
        return "<undefined>"


UNDEF = _Undefined()


def _opt(fn):
    """Evaluate a name lazily; unbound -> UNDEF."""
    try:
        return fn()
    except (NameError, UnboundLocalError):
        return UNDEF


def _is_traced_bool(x):
    import jax.core

    from ..core.tensor import Tensor

    raw = x._data if isinstance(x, Tensor) else x
    if isinstance(raw, jax.core.Tracer):
        return True, raw
    return False, raw


def _unwrap(v):
    from ..core.tensor import Tensor

    return v._data if isinstance(v, Tensor) else v


def _rewrap(raw, like):
    from ..core.tensor import Tensor

    return Tensor._wrap(raw) if isinstance(like, Tensor) else raw


def _is_slot_leaf(v):
    from ..core.tensor import Tensor

    return isinstance(v, Tensor) or v is UNDEF


def _unwrap_tree(v):
    """Unwrap a carry slot that may be a CONTAINER of Tensors (list
    accumulation patterns — list_transformer.py territory)."""
    import jax

    return jax.tree.map(_unwrap, v, is_leaf=_is_slot_leaf)


def _rewrap_tree(raw, like):
    import jax

    return jax.tree.map(_rewrap, raw, like, is_leaf=_is_slot_leaf)


def _wrap_outputs(outs):
    """Branch outputs normalize to Tensors for array leaves (including
    leaves inside list/tuple slots) so both branches produce one type
    scheme."""
    import jax

    from ..core.tensor import Tensor

    def w(o):
        return Tensor._wrap(o) if isinstance(o, jax.Array) else o

    return tuple(
        o if o is UNDEF else jax.tree.map(w, o, is_leaf=_is_slot_leaf)
        for o in outs)


def cond(pred, true_fn, false_fn, carry):
    """Runtime dispatch for a transformed `if`."""
    traced, raw = _is_traced_bool(pred)
    if not traced:
        return _wrap_outputs(true_fn(carry) if bool(raw) else
                             false_fn(carry))
    import jax
    import jax.numpy as jnp

    # traced predicate: lax.cond over the defined leaves; UNDEF slots pass
    # through statically (both branches must then produce real values)
    defined_idx = [i for i, v in enumerate(carry) if v is not UNDEF]

    def make(branch):
        def run(defined_raw):
            full = list(carry)
            for j, i in enumerate(defined_idx):
                full[i] = _rewrap_tree(defined_raw[j], carry[i])
            outs = branch(tuple(full))
            out_raw = tuple(_unwrap_tree(o) for o in outs)
            for o in out_raw:
                if o is UNDEF:
                    raise ValueError(
                        "dy2static: a variable assigned in only one "
                        "branch of a traced `if` must be defined in both "
                        "branches (or before the if)")
            return out_raw

        return run

    operand = tuple(_unwrap_tree(carry[i]) for i in defined_idx)
    out_raw = jax.lax.cond(jnp.reshape(raw, ()).astype(bool),
                           make(true_fn), make(false_fn), operand)
    return _wrap_outputs(out_raw)


def while_loop(cond_fn, body_fn, carry):
    """Runtime dispatch for a transformed `while`."""
    pred = cond_fn(carry)
    traced, raw = _is_traced_bool(pred)
    if not traced:
        while bool(_unwrap(pred)):
            carry = _wrap_outputs(body_fn(carry))
            pred = cond_fn(carry)
        return carry
    import jax
    import jax.numpy as jnp

    for v in carry:
        if v is UNDEF:
            raise ValueError(
                "dy2static: every variable used in a traced `while` must "
                "be initialized before the loop (XLA needs a fixed carry)")

    def lax_cond(c_raw):
        full = tuple(_rewrap_tree(r, o) for r, o in zip(c_raw, carry))
        return jnp.reshape(_unwrap(cond_fn(full)), ()).astype(bool)

    def lax_body(c_raw):
        full = tuple(_rewrap_tree(r, o) for r, o in zip(c_raw, carry))
        outs = body_fn(full)
        return tuple(_unwrap_tree(o) for o in outs)

    out_raw = jax.lax.while_loop(lax_cond, lax_body,
                                 tuple(_unwrap_tree(v) for v in carry))
    return _wrap_outputs(out_raw)


def _rt_indexable(it):
    """Iterables without __getitem__ (dict views, generators) materialize
    to a list so the for->while index rewrite can subscript them."""
    return it if hasattr(it, "__getitem__") else list(it)


def _rt_not(x):
    """`not` that stays correct on traced/array booleans
    (logical_transformer.py convert_logical_not)."""
    traced, raw = _is_traced_bool(x)
    if traced:
        import jax.numpy as jnp

        return jnp.logical_not(raw)
    if hasattr(raw, "dtype"):
        import numpy as np

        return np.logical_not(raw)
    return not raw


def _rt_bool(fn_a, fn_b, op_name):
    """Short-circuiting and/or over lazily-evaluated operands; traced
    operands combine via jnp.logical_* (both sides evaluated, as in the
    reference's convert_logical_and)."""
    a = fn_a()
    ta, ra = _is_traced_bool(a)
    if not ta and not hasattr(ra, "dtype"):
        if op_name == "and" and not ra:
            return ra
        if op_name == "or" and ra:
            return ra
    b = fn_b()
    tb, rb = _is_traced_bool(b)
    if ta or tb:
        import jax.numpy as jnp

        return (jnp.logical_and if op_name == "and"
                else jnp.logical_or)(ra, rb)
    if hasattr(ra, "dtype") or hasattr(rb, "dtype"):
        import numpy as np

        return (np.logical_and if op_name == "and"
                else np.logical_or)(ra, rb)
    return (ra and rb) if op_name == "and" else (ra or rb)


_JST = {"cond": cond, "while_loop": while_loop, "opt": _opt,
        "UNDEF": UNDEF}


class _NameCollector(ast.NodeVisitor):
    def __init__(self):
        self.names = []

    _HELPERS = ("__jst_true_", "__jst_false_", "__jst_wcond_",
                "__jst_wbody_", "__jst_carry")  # carry param name is
    # chosen to never prefix-collide with data flags (__jst_cont_*!)

    def _add(self, n):
        # generated helper FUNCTIONS never join a carry; generated data
        # names (__jst_it/brk/cont/ret/seq/stop/step) must
        if n not in self.names and not n.startswith(self._HELPERS):
            self.names.append(n)

    def visit_Name(self, node):
        if isinstance(node.ctx, (ast.Store,)):
            self._add(node.id)

    def visit_AugAssign(self, node):
        if isinstance(node.target, ast.Name):
            self._add(node.target.id)
        self.generic_visit(node)

    def visit_For(self, node):
        for t in ast.walk(node.target):
            if isinstance(t, ast.Name):
                self._add(t.id)
        self.generic_visit(node)

    # don't descend into nested function/class scopes
    def visit_FunctionDef(self, node):
        self._add(node.name)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self._add(node.name)

    def visit_Lambda(self, node):
        pass


def _assigned_names(stmts):
    c = _NameCollector()
    for s in stmts:
        c.visit(s)
    return c.names


def _has_flow_escape(node_or_stmts):
    """Conservatively: any break/continue/return in these statements,
    recursing into compound statements but NOT into nested function/class
    scopes (their control flow cannot escape into ours)."""
    stmts = node_or_stmts if isinstance(node_or_stmts, list) \
        else [node_or_stmts]
    for s in stmts:
        if isinstance(s, (ast.Return, ast.Break, ast.Continue)):
            return True
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda, ast.ClassDef)):
            continue
        for child in ast.iter_child_nodes(s):
            if _has_flow_escape(child):
                return True
    return False


def _names_in_expr(expr):
    return [n.id for n in ast.walk(expr) if isinstance(n, ast.Name)
            and isinstance(n.ctx, ast.Load)]


class _ControlFlowTransformer(ast.NodeTransformer):
    def __init__(self):
        self.counter = 0

    def _tuple(self, names, ctx):
        return ast.Tuple(
            elts=[ast.Name(id=n, ctx=ctx()) for n in names], ctx=ctx())

    def _branch_fn(self, fname, names, body):
        """def fname(__jst_c): (names) = __jst_c; body; return (names)"""
        stmts = []
        if names:
            stmts.append(ast.Assign(
                targets=[self._tuple(names, ast.Store)],
                value=ast.Name(id="__jst_carry", ctx=ast.Load())))
        stmts.extend(body)
        stmts.append(ast.Return(value=self._tuple(names, ast.Load)))
        return ast.FunctionDef(
            name=fname,
            args=ast.arguments(posonlyargs=[], args=[
                ast.arg(arg="__jst_carry")], kwonlyargs=[], kw_defaults=[],
                defaults=[]),
            body=stmts, decorator_list=[])

    def _opt_tuple(self, names):
        """(_jst_opt(lambda: a), _jst_opt(lambda: b), ...)"""
        elts = []
        for n in names:
            elts.append(ast.Call(
                func=ast.Name(id="__jst_opt", ctx=ast.Load()),
                args=[ast.Lambda(
                    args=ast.arguments(posonlyargs=[], args=[],
                                       kwonlyargs=[], kw_defaults=[],
                                       defaults=[]),
                    body=ast.Name(id=n, ctx=ast.Load()))],
                keywords=[]))
        return ast.Tuple(elts=elts, ctx=ast.Load())

    def visit_If(self, node):
        self.generic_visit(node)
        if _has_flow_escape(node.body) or _has_flow_escape(node.orelse):
            return node  # python semantics preserved; needs concrete pred
        names = _assigned_names(node.body + node.orelse)
        if not names:
            return node
        k = self.counter
        self.counter += 1
        tfn = self._branch_fn(f"__jst_true_{k}", names, node.body)
        ffn = self._branch_fn(
            f"__jst_false_{k}", names,
            node.orelse or [ast.Pass()])
        call = ast.Assign(
            targets=[self._tuple(names, ast.Store)],
            value=ast.Call(
                func=ast.Name(id="__jst_cond", ctx=ast.Load()),
                args=[node.test,
                      ast.Name(id=f"__jst_true_{k}", ctx=ast.Load()),
                      ast.Name(id=f"__jst_false_{k}", ctx=ast.Load()),
                      self._opt_tuple(names)],
                keywords=[]))
        return [tfn, ffn, call]

    def visit_While(self, node):
        self.generic_visit(node)
        if node.orelse or _has_flow_escape(node.body):
            return node
        names = _assigned_names(node.body)
        # (loop-invariant reads in the test close over the outer scope)
        if not names:
            return node
        k = self.counter
        self.counter += 1
        cond_stmts = []
        if names:
            cond_stmts.append(ast.Assign(
                targets=[self._tuple(names, ast.Store)],
                value=ast.Name(id="__jst_carry", ctx=ast.Load())))
        cond_stmts.append(ast.Return(value=node.test))
        cfn = ast.FunctionDef(
            name=f"__jst_wcond_{k}",
            args=ast.arguments(posonlyargs=[], args=[
                ast.arg(arg="__jst_carry")], kwonlyargs=[], kw_defaults=[],
                defaults=[]),
            body=cond_stmts, decorator_list=[])
        bfn = self._branch_fn(f"__jst_wbody_{k}", names, node.body)
        call = ast.Assign(
            targets=[self._tuple(names, ast.Store)],
            value=ast.Call(
                func=ast.Name(id="__jst_while", ctx=ast.Load()),
                args=[ast.Name(id=f"__jst_wcond_{k}", ctx=ast.Load()),
                      ast.Name(id=f"__jst_wbody_{k}", ctx=ast.Load()),
                      self._opt_tuple(names)],
                keywords=[]))
        return [cfn, bfn, call]


def _name(n, ctx=ast.Load):
    return ast.Name(id=n, ctx=ctx())


def _assign(target, value):
    return ast.Assign(targets=[_name(target, ast.Store)], value=value)


def _const(v):
    return ast.Constant(value=v)


def _not(expr):
    return ast.UnaryOp(op=ast.Not(), operand=expr)


def _and(*exprs):
    exprs = [e for e in exprs if e is not None]
    if len(exprs) == 1:
        return exprs[0]
    return ast.BoolOp(op=ast.And(), values=list(exprs))


class _ForToWhileTransformer(ast.NodeTransformer):
    """LoopTransformer's for-range half (dygraph_to_static/
    loop_transformer.py): `for i in range(...)` and `for x in tensor`
    become while loops so traced trip counts hit lax.while_loop. The
    iterator increments FIRST inside the body (starting one step back),
    so a later `continue`-guard rewrite cannot skip it."""

    def __init__(self):
        self.counter = 0

    def visit_For(self, node):
        self.generic_visit(node)
        if node.orelse:
            return node
        k = self.counter
        it, stop, step = f"__jst_it_{k}", f"__jst_stop_{k}", \
            f"__jst_step_{k}"
        is_range = (isinstance(node.iter, ast.Call)
                    and isinstance(node.iter.func, ast.Name)
                    and node.iter.func.id == "range"
                    and 1 <= len(node.iter.args) <= 3
                    and not node.iter.keywords)
        prelude = []
        if is_range:
            a = node.iter.args
            start = a[0] if len(a) >= 2 else _const(0)
            stop_e = a[1] if len(a) >= 2 else a[0]
            step_e = a[2] if len(a) == 3 else _const(1)
            if len(a) == 3 and not (isinstance(step_e, ast.Constant)
                                    and isinstance(step_e.value, int)
                                    and step_e.value > 0):
                return node  # non-positive/dynamic step: keep python for
            assigns = [_assign(it, ast.BinOp(left=start, op=ast.Sub(),
                                             right=_name(step)))]
            bind = [ast.Assign(targets=[node.target],
                               value=_name(it))]
        elif isinstance(node.target, ast.Name):
            # for x in seq: iterate the leading axis by index (tensor
            # iteration unrolls statically only via len(), which is a
            # static shape even for traced arrays)
            seq = f"__jst_seq_{k}"
            prelude.append(_assign(seq, ast.Call(
                func=_name("__jst_indexable"), args=[node.iter],
                keywords=[])))
            start = _const(0)
            stop_e = ast.Call(func=_name("len"), args=[_name(seq)],
                              keywords=[])
            step_e = _const(1)
            assigns = [_assign(it, _const(-1))]
            bind = [ast.Assign(
                targets=[node.target],
                value=ast.Subscript(value=_name(seq),
                                    slice=_name(it), ctx=ast.Load()))]
        else:
            return node
        self.counter += 1
        prelude.extend([
            _assign(stop, stop_e),
            _assign(step, step_e),
        ] + assigns)
        body = [ast.AugAssign(target=_name(it, ast.Store),
                              op=ast.Add(), value=_name(step))] \
            + bind + node.body
        test = ast.Compare(
            left=ast.BinOp(left=_name(it), op=ast.Add(),
                           right=_name(step)),
            ops=[ast.Lt()], comparators=[_name(stop)])
        return prelude + [ast.While(test=test, body=body, orelse=[])]


def _contains(stmts, kinds, cross_loops=False):
    """Any of `kinds` in these statements, not descending into nested
    function/class scopes, and (unless cross_loops) not into nested
    loops (whose break/continue bind tighter; returns DO escape)."""
    want_return = (ast.Return in kinds) if isinstance(kinds, tuple) \
        else kinds is ast.Return
    for s in stmts if isinstance(stmts, list) else [stmts]:
        if isinstance(s, kinds):
            return True
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda, ast.ClassDef)):
            continue
        if not cross_loops and isinstance(s, (ast.While, ast.For)):
            if want_return and _contains(s.body, ast.Return,
                                         cross_loops=True):
                return True
            continue
        for child in ast.iter_child_nodes(s):
            if _contains([child], kinds, cross_loops):
                return True
    return False


class _EarlyExitTransformer(ast.NodeTransformer):
    """break_continue_transformer.py + return_transformer.py in one
    pass: rewrite `break`/`continue` into guard flags and loop-returns
    into a single-exit form, so the loops become escape-free and the
    cond/while transformer can stage them onto lax control flow."""

    RET_FLAG = "__jst_ret_flag"
    RET_VAL = "__jst_ret_val"

    def __init__(self):
        self.counter = 0
        self.uses_return = False

    # -- statement-list guarding ------------------------------------
    def _sets_flags(self, s, flags):
        for node in ast.walk(s):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id in flags:
                        return True
        return False

    def _guard_rest(self, stmts, flags):
        """After any compound statement that may set a guard flag, wrap
        the remaining statements in `if not (f1 or f2 ...)`."""
        out = []
        for i, s in enumerate(stmts):
            out.append(s)
            rest = stmts[i + 1:]
            if rest and not isinstance(s, (ast.Break, ast.Continue,
                                           ast.Return)) \
                    and self._sets_flags(s, flags):
                cond = _not(ast.BoolOp(
                    op=ast.Or(),
                    values=[_name(f) for f in sorted(flags)])
                    if len(flags) > 1 else _name(next(iter(flags))))
                out.append(ast.If(test=cond,
                                  body=self._guard_rest(rest, flags),
                                  orelse=[]))
                return out
        return out

    def _replace_escapes(self, stmts, brk, cont, in_loop):
        """Replace break/continue/return statements with flag sets (not
        descending into nested loops for break/continue, nor nested
        scopes at all)."""
        new = []
        for s in stmts:
            if isinstance(s, ast.Break) and brk:
                new.append(_assign(brk, _const(True)))
            elif isinstance(s, ast.Continue) and cont:
                new.append(_assign(cont, _const(True)))
            elif isinstance(s, ast.Return) and in_loop \
                    and self.uses_return:
                new.append(_assign(self.RET_VAL,
                                   s.value or _const(None)))
                new.append(_assign(self.RET_FLAG, _const(True)))
            elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                new.append(s)
            elif isinstance(s, (ast.While, ast.For)):
                # nested loop: its own break/continue bind to it; only
                # returns keep propagating (handled when it is visited)
                new.append(s)
            elif isinstance(s, ast.If):
                s.body = self._replace_escapes(s.body, brk, cont,
                                               in_loop)
                s.orelse = self._replace_escapes(s.orelse, brk, cont,
                                                 in_loop)
                new.append(s)
            else:
                new.append(s)
        return new

    def visit_While(self, node):
        self.generic_visit(node)  # inner loops first
        has_brk = _contains(node.body, ast.Break)
        has_cont = _contains(node.body, ast.Continue)
        has_ret = self.uses_return and _contains(
            node.body, ast.Return, cross_loops=True)
        if not (has_brk or has_cont or has_ret):
            return node
        k = self.counter
        self.counter += 1
        brk = f"__jst_brk_{k}" if (has_brk or has_ret) else None
        cont = f"__jst_cont_{k}" if has_cont else None
        body = self._replace_escapes(node.body, brk, cont, True)
        flags = set()
        if brk:
            flags.add(brk)
        if cont:
            flags.add(cont)
        if has_ret:
            flags.add(self.RET_FLAG)
        body = self._guard_rest(body, flags)
        if cont:
            body = [_assign(cont, _const(False))] + body
        prelude = []
        test = node.test
        if cont:
            # also initialized BEFORE the loop: a traced lax.while_loop
            # needs every carried name bound in the initial carry
            prelude.append(_assign(cont, _const(False)))
        if brk:
            prelude.append(_assign(brk, _const(False)))
            test = _and(_not(_name(brk)), test)
        if has_ret:
            test = _and(_not(_name(self.RET_FLAG)), test)
        return prelude + [ast.While(test=test, body=body, orelse=[])]

    def apply(self, fdef):
        # single-exit rewrite only when a loop contains a return
        loops = [n for n in ast.walk(fdef)
                 if isinstance(n, (ast.While, ast.For))]
        self.uses_return = any(
            _contains(lp.body, ast.Return, cross_loops=True)
            for lp in loops)
        if self.uses_return:
            # replace every top-level-reachable return with flag sets,
            # then a single trailing return
            def repl_fn_returns(stmts):
                new = []
                for s in stmts:
                    if isinstance(s, ast.Return):
                        new.append(_assign(self.RET_VAL,
                                           s.value or _const(None)))
                        new.append(_assign(self.RET_FLAG, _const(True)))
                    elif isinstance(s, ast.If):
                        s.body = repl_fn_returns(s.body)
                        s.orelse = repl_fn_returns(s.orelse)
                        new.append(s)
                    else:
                        new.append(s)
                return new

            fdef.body = repl_fn_returns(fdef.body)
        self.visit(fdef)
        if self.uses_return:
            fdef.body = [
                _assign(self.RET_FLAG, _const(False)),
                _assign(self.RET_VAL, _const(None)),
            ] + self._guard_rest(fdef.body, {self.RET_FLAG}) + [
                ast.Return(value=_name(self.RET_VAL))]
        return fdef


class _LogicalTransformer(ast.NodeTransformer):
    """and/or/not -> runtime __jst_and/__jst_or/__jst_not calls so
    boolean logic works on traced values (the reference's
    logical_transformer.py). Operands stay lazily evaluated via lambdas
    to preserve python short-circuiting."""

    def _lam(self, expr):
        return ast.Lambda(
            args=ast.arguments(posonlyargs=[], args=[], kwonlyargs=[],
                               kw_defaults=[], defaults=[]),
            body=expr)

    def visit_BoolOp(self, node):
        self.generic_visit(node)
        name = "__jst_and" if isinstance(node.op, ast.And) else "__jst_or"
        out = node.values[0]
        for nxt in node.values[1:]:
            out = ast.Call(func=_name(name),
                           args=[self._lam(out), self._lam(nxt)],
                           keywords=[])
        return out

    def visit_UnaryOp(self, node):
        self.generic_visit(node)
        if isinstance(node.op, ast.Not):
            return ast.Call(func=_name("__jst_not"),
                            args=[node.operand], keywords=[])
        return node


_CONVERTED = {}


def _rt_print(*args, **kw):
    """print() that stays functional under trace (print_transformer.py
    role): traced operands route through jax.debug.print so the values
    appear at RUN time, not trace time."""
    import jax

    vals = [_unwrap(a) for a in args]
    if any(isinstance(v, jax.core.Tracer) for v in vals):
        fmt = kw.get("sep", " ").join("{}" for _ in vals)
        jax.debug.print(fmt, *vals)
    else:
        print(*args, **kw)


def _rt_assert(pred, msg_fn=None):
    """assert that works on tensors and under trace
    (assert_transformer.py / assert_op.cc role): concrete values reduce
    with .all() like the Assert op; traced predicates check at run time
    via a host callback (surfacing as a backend callback error WRAPPING
    the AssertionError — callers matching AssertionError only catch the
    concrete path).

    msg_fn is a thunk so the message expression is only evaluated on
    failure, like a real assert."""
    traced, raw = _is_traced_bool(pred)
    if not traced:
        ok = raw.all() if hasattr(raw, "all") else raw
        assert bool(ok), (msg_fn() if msg_fn is not None else None)
        return
    import jax
    import numpy as _np

    try:  # evaluate the message at trace time: the callback must not
        msg = msg_fn() if msg_fn is not None else None  # hold tracers
    except Exception:
        msg = None

    def _check(ok):
        if not bool(_np.asarray(ok).all()):
            raise AssertionError(
                msg if msg is not None else "Assert failed in traced code")

    jax.debug.callback(_check, raw)


def _rt_cast(v, py_type):
    """int()/float()/bool() that stage instead of concretizing
    (cast_transformer.py role): traced tensors become dtype casts."""
    import jax

    raw = _unwrap(v)
    if isinstance(raw, jax.core.Tracer):
        import jax.numpy as jnp

        dt = {int: jnp.int64, float: jnp.float32,
              bool: jnp.bool_}[py_type]
        return _rewrap(raw.astype(dt), v)
    return py_type(raw)


def _rt_list_append(lst, v):
    """Staged list append (list_transformer.py role): rebinding instead
    of mutating lets the control-flow carry analysis see the list, so
    appends inside traced if/while branches ride the lax carry."""
    if isinstance(lst, list):
        return lst + [v]
    lst.append(v)          # non-list .append (e.g. LayerList): passthru
    return lst


def _rt_list_pop(lst, *idx):
    if isinstance(lst, list):
        i = idx[0] if idx else -1
        return lst[:i] + lst[i:][1:], lst[i]
    return lst, lst.pop(*idx)


class _ListTransformer(ast.NodeTransformer):
    """`lst.append(v)` / `lst.pop(i)` statements become REBINDING calls
    (list_transformer.py's tensor-array rewrite, runtime-staged): the
    list variable is assigned on every mutation, which puts it into the
    if/while carry computed by the later control-flow transforms.

    ONLY lists the function owns are rewritten — names first bound to a
    list literal in the body. Rebinding a parameter/closure/global list
    would silently stop mutating the caller's object (or raise
    UnboundLocalError for closures)."""

    def visit_FunctionDef(self, node):
        params = {a.arg for a in node.args.args + node.args.kwonlyargs}
        if node.args.vararg:
            params.add(node.args.vararg.arg)
        if node.args.kwarg:
            params.add(node.args.kwarg.arg)
        own = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                    and isinstance(sub.targets[0], ast.Name) \
                    and isinstance(sub.value, (ast.List, ast.ListComp)):
                own.add(sub.targets[0].id)
        self._own = own - params
        self.generic_visit(node)
        return node

    def _target(self, call):
        if (isinstance(call, ast.Call)
                and isinstance(call.func, ast.Attribute)
                and isinstance(call.func.value, ast.Name)
                and call.func.value.id in getattr(self, "_own", ())
                and call.func.attr in ("append", "pop")):
            return call.func.value.id, call.func.attr
        return None, None

    def visit_Expr(self, node):
        self.generic_visit(node)
        name, kind = self._target(node.value)
        if kind == "append":
            return ast.Assign(
                targets=[ast.Name(id=name, ctx=ast.Store())],
                value=ast.Call(
                    func=ast.Name(id="__jst_list_append", ctx=ast.Load()),
                    args=[ast.Name(id=name, ctx=ast.Load())]
                    + node.value.args, keywords=[]))
        if kind == "pop":
            return ast.Assign(
                targets=[ast.Tuple(
                    elts=[ast.Name(id=name, ctx=ast.Store()),
                          ast.Name(id="__jst_popped__", ctx=ast.Store())],
                    ctx=ast.Store())],
                value=ast.Call(
                    func=ast.Name(id="__jst_list_pop", ctx=ast.Load()),
                    args=[ast.Name(id=name, ctx=ast.Load())]
                    + node.value.args, keywords=[]))
        return node

    def visit_Assign(self, node):
        self.generic_visit(node)
        name, kind = self._target(node.value)
        if kind == "pop" and len(node.targets) == 1:
            return ast.Assign(
                targets=[ast.Tuple(
                    elts=[ast.Name(id=name, ctx=ast.Store()),
                          node.targets[0]], ctx=ast.Store())],
                value=ast.Call(
                    func=ast.Name(id="__jst_list_pop", ctx=ast.Load()),
                    args=[ast.Name(id=name, ctx=ast.Load())]
                    + node.value.args, keywords=[]))
        return node


class _BuiltinCallTransformer(ast.NodeTransformer):
    """print/assert/int/float/bool rewrites (print_transformer.py,
    assert_transformer.py, cast_transformer.py counterparts): each
    becomes a runtime-dispatch call that behaves like the builtin on
    concrete values and stages on traced ones. Names the function
    SHADOWS (params or local assignments) are left untouched."""

    def visit_FunctionDef(self, node):
        shadowed = {a.arg for a in node.args.args + node.args.kwonlyargs}
        if node.args.vararg:
            shadowed.add(node.args.vararg.arg)
        if node.args.kwarg:
            shadowed.add(node.args.kwarg.arg)
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx,
                                                        ast.Store):
                shadowed.add(sub.id)
        self._shadowed = shadowed
        self.generic_visit(node)
        return node

    def _is_builtin(self, name):
        return name not in getattr(self, "_shadowed", ())

    def visit_Call(self, node):
        self.generic_visit(node)
        if isinstance(node.func, ast.Name) and \
                self._is_builtin(node.func.id):
            if node.func.id == "print":
                return ast.Call(
                    func=ast.Name(id="__jst_print", ctx=ast.Load()),
                    args=node.args, keywords=node.keywords)
            if node.func.id in ("int", "float", "bool") \
                    and len(node.args) == 1 and not node.keywords:
                return ast.Call(
                    func=ast.Name(id="__jst_cast", ctx=ast.Load()),
                    args=[node.args[0],
                          ast.Name(id=node.func.id, ctx=ast.Load())],
                    keywords=[])
        return node

    def visit_Assert(self, node):
        self.generic_visit(node)
        # the message rides as a THUNK so it is only evaluated on
        # failure (a real assert never touches it on the passing path)
        msg = ast.Lambda(
            args=ast.arguments(posonlyargs=[], args=[], vararg=None,
                               kwonlyargs=[], kw_defaults=[],
                               kwarg=None, defaults=[]),
            body=node.msg) if node.msg is not None else \
            ast.Constant(value=None)
        return ast.Expr(value=ast.Call(
            func=ast.Name(id="__jst_assert", ctx=ast.Load()),
            args=[node.test, msg], keywords=[]))


class _SuperRewriter(ast.NodeTransformer):
    """Zero-arg super() relies on the implicit __class__ closure cell,
    which an exec-recompiled function lacks; rewrite to the explicit
    two-arg form bound to the original class."""

    def __init__(self, first_arg):
        self.first_arg = first_arg
        self.used = False

    def visit_Call(self, node):
        self.generic_visit(node)
        if isinstance(node.func, ast.Name) and node.func.id == "super" \
                and not node.args and self.first_arg:
            self.used = True
            node.args = [ast.Name(id="__jst_class__", ctx=ast.Load()),
                         ast.Name(id=self.first_arg, ctx=ast.Load())]
        return node


def convert_to_static(fn):
    """Return a control-flow-converted version of `fn` (cached). Falls
    back to the original on any source/AST failure (builtins, C
    functions, exotic syntax)."""
    cached = _CONVERTED.get(fn)
    if cached is not None:
        return cached
    try:
        src = textwrap.dedent(inspect.getsource(fn))
        tree = ast.parse(src)
        fdef = tree.body[0]
        fdef.decorator_list = []
        first_arg = fdef.args.args[0].arg if fdef.args.args else None
        sup = _SuperRewriter(first_arg)
        sup.visit(fdef)
        fdef = _BuiltinCallTransformer().visit(fdef)
        fdef = _ListTransformer().visit(fdef)
        fdef = _ForToWhileTransformer().visit(fdef)
        fdef = _EarlyExitTransformer().apply(fdef)
        fdef = _LogicalTransformer().visit(fdef)
        new = _ControlFlowTransformer().visit(fdef)
        mod = ast.Module(body=[new], type_ignores=[])
        ast.fix_missing_locations(mod)
        glb = dict(fn.__globals__)
        if sup.used:
            cls = None
            if fn.__closure__ and "__class__" in fn.__code__.co_freevars:
                cell = fn.__closure__[
                    fn.__code__.co_freevars.index("__class__")]
                try:
                    cls = cell.cell_contents
                except ValueError:
                    pass
            if cls is None:
                raise TypeError("zero-arg super() without __class__ cell")
            glb["__jst_class__"] = cls
        glb["__jst_cond"] = cond
        glb["__jst_while"] = while_loop
        glb["__jst_opt"] = _opt
        glb["__jst_not"] = _rt_not
        glb["__jst_indexable"] = _rt_indexable
        glb["__jst_and"] = functools.partial(_rt_bool, op_name="and")
        glb["__jst_or"] = functools.partial(_rt_bool, op_name="or")
        glb["__jst_list_append"] = _rt_list_append
        glb["__jst_list_pop"] = _rt_list_pop
        glb["__jst_print"] = _rt_print
        glb["__jst_assert"] = _rt_assert
        glb["__jst_cast"] = _rt_cast
        # closures: bind current cell values by name (static snapshot)
        if fn.__closure__:
            for name, cell in zip(fn.__code__.co_freevars, fn.__closure__):
                try:
                    glb[name] = cell.cell_contents
                except ValueError:
                    pass
        code = compile(mod, filename=f"<dy2static {fn.__qualname__}>",
                       mode="exec")
        ns = {}
        exec(code, glb, ns)
        out = ns[fdef.name]
        out = functools.wraps(fn)(out)
        out.__wrapped_original__ = fn
    except (OSError, TypeError, SyntaxError):
        out = fn
    _CONVERTED[fn] = out
    return out
