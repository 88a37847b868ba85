"""Per-function control-flow graphs for the dataflow rules.

`build_cfg(fn_node)` lowers one function body to basic blocks of
ATOMS — simple statements kept whole, compound statements decomposed
into their control expressions (an `if` contributes its test, a `for`
contributes the For node itself so transfer functions see the
target-from-iter binding, a `try` contributes nothing but edges).
Nested function/class definitions are single opaque atoms: a CFG never
crosses a scope boundary.

Edges model:

  * branches (`if`/`else`), loops (back edges, `break`/`continue`,
    `orelse`), `while`;
  * `try`/`except`/`else`/`finally`: every atom inside a `try` body
    gets an out-edge to each handler entry (an exception can interrupt
    the body at any statement, so handler in-states join the state at
    EVERY point of the body), handlers and the normal path route
    through `finally`;
  * exception exits: `raise` and a failing `assert` jump to the
    innermost enclosing handlers, or to the function's dedicated
    `raise_exit` block when uncaught — so "all paths out of the
    function" includes the paths an exception takes. Implicit
    exceptions from arbitrary calls are NOT modeled (every call site
    would otherwise be an edge, drowning the analysis in paths that
    cannot leak anything they did not already own).

Two virtual empty blocks terminate every CFG: `exit` (normal return or
falling off the end) and `raise_exit` (uncaught exception). Both are
real blocks so forward analyses observe the state on every way out.

Approximations (conservative for may-analyses, documented here so
rules don't re-derive them): `finally` bodies appear once and fall
through to both the normal continuation and the exception
continuation; `with` does not model `__exit__` suppressing exceptions;
`break`/`continue` bypass `finally` routing.
"""
from __future__ import annotations

import ast
import dataclasses

# statements that open a new scope: atoms, never descended into
SCOPE_STMTS = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
SCOPE_BOUNDARY = SCOPE_STMTS + (ast.Lambda,)


@dataclasses.dataclass
class Block:
    bid: int
    atoms: list[ast.AST] = dataclasses.field(default_factory=list)
    succs: set[int] = dataclasses.field(default_factory=set)


@dataclasses.dataclass
class CFG:
    blocks: dict[int, Block]
    entry: int
    exit: int           # normal return / fall-off-the-end
    raise_exit: int     # uncaught exception leaves the function

    def preds(self) -> dict[int, set[int]]:
        out: dict[int, set[int]] = {b: set() for b in self.blocks}
        for b in self.blocks.values():
            for s in b.succs:
                out[s].add(b.bid)
        return out


def shallow_walk(node: ast.AST):
    """`ast.walk` that never crosses into a nested scope (function,
    lambda, class) — the expression-level view of one atom. The
    boundary node itself is yielded (so a nested `def` atom is
    visible), its body is not. A `for` or `with` atom is its header
    (target and iterable, context items): the statements of its body
    are atoms of their own."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, SCOPE_BOUNDARY):
            # even as the root, a scope's body belongs to the inner CFG
            continue
        if isinstance(n, (ast.For, ast.AsyncFor)):
            stack.extend((n.target, n.iter))
        elif isinstance(n, (ast.With, ast.AsyncWith)):
            stack.extend(n.items)
        else:
            stack.extend(ast.iter_child_nodes(n))


def atom_bindings(atom: ast.AST) -> list[tuple[list[ast.AST], ast.AST | None]]:
    """(targets, value) pairs an atom binds: assignments, loop targets
    (bound from the iterable), `with ... as` names, except-handler
    names. Transfer functions use this instead of re-matching node
    types."""
    if isinstance(atom, ast.Assign):
        return [(list(atom.targets), atom.value)]
    if isinstance(atom, ast.AugAssign):
        return [([atom.target], atom.value)]
    if isinstance(atom, ast.AnnAssign):
        return [([atom.target], atom.value)] if atom.value is not None else []
    if isinstance(atom, (ast.For, ast.AsyncFor)):
        return [([atom.target], atom.iter)]
    if isinstance(atom, (ast.With, ast.AsyncWith)):
        return [([it.optional_vars], it.context_expr)
                for it in atom.items if it.optional_vars is not None]
    if isinstance(atom, ast.ExceptHandler) and atom.name:
        return [([ast.Name(id=atom.name, ctx=ast.Store())], None)]
    if isinstance(atom, (ast.NamedExpr,)):
        return [([atom.target], atom.value)]
    return []


class _Builder:
    def __init__(self):
        self.blocks: dict[int, Block] = {}
        self._n = 0
        self.exit = self._new().bid
        self.raise_exit = self._new().bid
        # innermost-first stacks
        self._handlers: list[list[int]] = []   # except-entry block ids
        self._loops: list[tuple[int, int]] = []  # (header, after)

    def _new(self) -> Block:
        b = Block(bid=self._n)
        self._n += 1
        self.blocks[b.bid] = b
        return b

    def _edge(self, a: int, b: int) -> None:
        self.blocks[a].succs.add(b)

    def _raise_targets(self) -> list[int]:
        return self._handlers[-1] if self._handlers else [self.raise_exit]

    # `cur` is the open block id; every method returns the open block
    # continuing the normal path, or None when the path terminated
    # (return/raise/break/continue).

    def _seq(self, stmts: list[ast.stmt], cur: int | None) -> int | None:
        for s in stmts:
            if cur is None:
                # unreachable code after return/raise: still built (a
                # rule may want its atoms) but disconnected
                cur = self._new().bid
            cur = self._stmt(s, cur)
        return cur

    def _stmt(self, s: ast.stmt, cur: int) -> int | None:
        in_try = bool(self._handlers)

        def put(atom: ast.AST, b: int) -> int:
            self.blocks[b].atoms.append(atom)
            if in_try:
                # the exception can fire at any atom: close the block
                # so its out-state reaches the handlers
                for h in self._handlers[-1]:
                    self._edge(b, h)
                nxt = self._new().bid
                self._edge(b, nxt)
                return nxt
            return b

        if isinstance(s, ast.Return):
            self.blocks[cur].atoms.append(s)
            self._edge(cur, self.exit)
            return None
        if isinstance(s, ast.Raise):
            self.blocks[cur].atoms.append(s)
            for t in self._raise_targets():
                self._edge(cur, t)
            return None
        if isinstance(s, ast.Assert):
            cur = put(s, cur)
            for t in self._raise_targets():
                self._edge(cur, t)
            nxt = self._new().bid
            self._edge(cur, nxt)
            return nxt
        if isinstance(s, ast.Break):
            if self._loops:
                self._edge(cur, self._loops[-1][1])
            return None
        if isinstance(s, ast.Continue):
            if self._loops:
                self._edge(cur, self._loops[-1][0])
            return None
        if isinstance(s, ast.If):
            cur = put(s.test, cur)
            after = self._new().bid
            then_end = self._seq(s.body, self._branch(cur))
            if then_end is not None:
                self._edge(then_end, after)
            if s.orelse:
                else_end = self._seq(s.orelse, self._branch(cur))
                if else_end is not None:
                    self._edge(else_end, after)
            else:
                self._edge(cur, after)
            return after
        if isinstance(s, ast.While):
            header = self._new().bid
            self._edge(cur, header)
            header = put(s.test, header)
            after = self._new().bid
            self._loops.append((header, after))
            body_end = self._seq(s.body, self._branch(header))
            self._loops.pop()
            if body_end is not None:
                self._edge(body_end, header)
            if s.orelse:
                else_end = self._seq(s.orelse, self._branch(header))
                if else_end is not None:
                    self._edge(else_end, after)
            else:
                self._edge(header, after)
            return after
        if isinstance(s, (ast.For, ast.AsyncFor)):
            header = self._new().bid
            self._edge(cur, header)
            header = put(s, header)   # the For node: target-from-iter
            after = self._new().bid
            self._loops.append((header, after))
            body_end = self._seq(s.body, self._branch(header))
            self._loops.pop()
            if body_end is not None:
                self._edge(body_end, header)
            if s.orelse:
                else_end = self._seq(s.orelse, self._branch(header))
                if else_end is not None:
                    self._edge(else_end, after)
            else:
                self._edge(header, after)
            return after
        if isinstance(s, (ast.With, ast.AsyncWith)):
            cur = put(s, cur)          # the With node: `as` bindings
            return self._seq(s.body, cur)
        if isinstance(s, ast.Try):
            return self._try(s, cur)
        if isinstance(s, ast.Match):
            # match: each case is a branch from the subject
            cur = put(s.subject, cur)
            after = self._new().bid
            for case in s.cases:
                end = self._seq(case.body, self._branch(cur))
                if end is not None:
                    self._edge(end, after)
            self._edge(cur, after)     # no case may match
            return after
        # simple statement (incl. nested def/class as opaque atoms)
        return put(s, cur)

    def _branch(self, frm: int) -> int:
        b = self._new()
        self._edge(frm, b.bid)
        return b.bid

    def _try(self, s: ast.Try, cur: int) -> int | None:
        after = self._new().bid
        # where does the normal/handled path continue? through finally
        if s.finalbody:
            fin_entry = self._new().bid
            fin_end = self._seq(s.finalbody, fin_entry)
            if fin_end is not None:
                self._edge(fin_end, after)
                # exception continuation: the finally also sits on the
                # propagation path out of the try
                for t in self._raise_targets():
                    self._edge(fin_end, t)
            done = fin_entry
        else:
            done = after
        handler_entries: list[int] = []
        handler_blocks: list[tuple[int, ast.ExceptHandler]] = []
        for h in s.handlers:
            hb = self._new()
            hb.atoms.append(h)         # binds `except E as name`
            handler_entries.append(hb.bid)
            handler_blocks.append((hb.bid, h))
        if not handler_entries and s.finalbody:
            # try/finally with no except: the finally entry IS the
            # exception target, so body exceptions route through it
            # (fin_end above already continues to the outer raise
            # targets as the propagation path)
            handler_entries = [done]
        if handler_entries:
            self._handlers.append(handler_entries)
        body_end = self._seq(s.body, self._branch(cur))
        if handler_entries:
            self._handlers.pop()
        if body_end is not None:
            body_end = self._seq(s.orelse, body_end)
        if body_end is not None:
            self._edge(body_end, done)
        for hb, h in handler_blocks:
            h_end = self._seq(h.body, self._branch(hb))
            if h_end is not None:
                self._edge(h_end, done)
        return after


def build_cfg(fn_node: ast.AST) -> CFG:
    """CFG of one function's body. `fn_node` is a FunctionDef /
    AsyncFunctionDef (or any node with a statement-list `body`)."""
    b = _Builder()
    entry = b._new().bid
    end = b._seq(list(fn_node.body), entry)
    if end is not None:
        b._edge(end, b.exit)
    return CFG(blocks=b.blocks, entry=entry, exit=b.exit,
               raise_exit=b.raise_exit)
