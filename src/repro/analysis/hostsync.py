"""Host-sync rule family.

A jitted/shard_mapped hot path must never force a device→host transfer
mid-trace: ``.item()``, builtin ``int()/float()/bool()`` on a traced
value, and ``np.asarray`` on a tracer all either fail under jit or —
worse — silently sync and serialize the device stream when the value is
concrete (e.g. under ``io_callback`` or during warm-up). These rules
find the *traced regions* in a file (functions decorated with or passed
to ``jax.jit`` / ``shard_map`` / ``jax.lax`` control-flow combinators,
including lambdas) and flag host-sync constructs applied to the region's
parameters (the traced values).

Attribute chains that stay static under trace — ``x.shape``, ``x.ndim``,
``x.size``, ``x.dtype`` — are exempt: ``int(x.shape[0])`` is fine,
``int(x[0])`` is not.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from repro.analysis.framework import (
    FileContext,
    Finding,
    resolve_name,
    rule,
)

_JIT_NAMES = {"jax.jit", "jit"}
_SHARD_MAP_NAMES = {
    "jax.shard_map",
    "jax.experimental.shard_map.shard_map",
    "shard_map",
}
#: combinator dotted name -> indices of its function-valued arguments
_COMBINATOR_FN_ARGS = {
    "jax.lax.scan": (0,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.fori_loop": (2,),
    "jax.lax.cond": (1, 2),
    "jax.lax.map": (0,),
}
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype"}


def _is_jit_like(name: Optional[str]) -> bool:
    return name in _JIT_NAMES or name in _SHARD_MAP_NAMES


def _decorator_is_traced(dec: ast.AST, aliases: Dict[str, str]) -> bool:
    if _is_jit_like(resolve_name(dec, aliases)):
        return True
    if isinstance(dec, ast.Call):
        name = resolve_name(dec.func, aliases)
        if _is_jit_like(name):
            return True
        if name == "functools.partial" and dec.args:
            return _is_jit_like(resolve_name(dec.args[0], aliases))
    return False


def find_traced_regions(ctx: FileContext) -> List[Tuple[ast.AST, str]]:
    """All (function node, how) regions whose body runs under trace."""
    defs: Dict[str, ast.FunctionDef] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs[node.name] = node

    regions: List[Tuple[ast.AST, str]] = []
    seen: Set[int] = set()

    def add(fn_node: ast.AST, how: str) -> None:
        if isinstance(fn_node, ast.Name):
            fn_node = defs.get(fn_node.id)
            if fn_node is None:
                return
        if isinstance(fn_node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)) and id(fn_node) not in seen:
            seen.add(id(fn_node))  # repro-lint: disable=determinism/id-keyed-cache
            regions.append((fn_node, how))

    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _decorator_is_traced(dec, ctx.aliases):
                    add(node, "decorated")
        elif isinstance(node, ast.Call):
            name = resolve_name(node.func, ctx.aliases)
            if name is None:
                continue
            if _is_jit_like(name) and node.args:
                add(node.args[0], name.rsplit(".", 1)[-1])
            elif name in _COMBINATOR_FN_ARGS:
                for i in _COMBINATOR_FN_ARGS[name]:
                    if i < len(node.args):
                        add(node.args[i], name)
    return regions


def _param_names(fn: ast.AST) -> Set[str]:
    args = fn.args
    names = {a.arg for a in args.args + args.posonlyargs + args.kwonlyargs}
    if args.vararg:
        names.add(args.vararg.arg)
    if args.kwarg:
        names.add(args.kwarg.arg)
    return names


def _references_traced(expr: ast.AST, params: Set[str],
                       aliases: Dict[str, str]) -> bool:
    """Does ``expr`` (an argument subtree) touch a traced value — a region
    parameter outside a static ``.shape``-style chain, or a jnp/jax call?"""
    parents: Dict[int, ast.AST] = {}
    for node in ast.walk(expr):
        for child in ast.iter_child_nodes(node):
            parents[id(child)] = node  # repro-lint: disable=determinism/id-keyed-cache

    def in_static_chain(node: ast.AST) -> bool:
        cur = node
        while True:
            parent = parents.get(id(cur))  # repro-lint: disable=determinism/id-keyed-cache
            if parent is None:
                return False
            if isinstance(parent, ast.Attribute) and parent.value is cur:
                if parent.attr in _STATIC_ATTRS:
                    return True
                cur = parent
                continue
            if isinstance(parent, ast.Subscript) and parent.value is cur:
                cur = parent
                continue
            return False

    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and node.id in params:
            if not in_static_chain(node):
                return True
        elif isinstance(node, ast.Call):
            name = resolve_name(node.func, aliases)
            if name and (name.startswith("jax.") or name.startswith("jax.numpy")):
                return True
    return False


def _body_nodes(fn: ast.AST) -> Iterator[ast.AST]:
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for stmt in body:
        yield from ast.walk(stmt)


@rule("host-sync/item",
      ".item() host transfer inside a traced region")
def check_item(ctx: FileContext) -> Iterator[Finding]:
    for fn, how in find_traced_regions(ctx):
        label = getattr(fn, "name", "<lambda>")
        for node in _body_nodes(fn):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                yield ctx.finding(
                    "host-sync/item", node,
                    f"{label} (traced via {how}): .item() forces a device→host "
                    f"sync; keep the value on device or move it out of the "
                    f"traced region",
                )


@rule("host-sync/host-cast",
      "int()/float()/bool() on a traced value inside a traced region")
def check_host_cast(ctx: FileContext) -> Iterator[Finding]:
    for fn, how in find_traced_regions(ctx):
        label = getattr(fn, "name", "<lambda>")
        params = _param_names(fn)
        for node in _body_nodes(fn):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                    and node.func.id in ("int", "float", "bool")
                    and len(node.args) == 1
                    and _references_traced(node.args[0], params, ctx.aliases)):
                yield ctx.finding(
                    "host-sync/host-cast", node,
                    f"{label} (traced via {how}): {node.func.id}() on a traced "
                    f"value raises ConcretizationTypeError under jit; use "
                    f"jnp casts (x.astype) or hoist to the host side",
                )


@rule("host-sync/np-on-tracer",
      "np.asarray/np.array of a traced value inside a traced region")
def check_np_on_tracer(ctx: FileContext) -> Iterator[Finding]:
    for fn, how in find_traced_regions(ctx):
        label = getattr(fn, "name", "<lambda>")
        params = _param_names(fn)
        for node in _body_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            name = resolve_name(node.func, ctx.aliases)
            if name in ("numpy.asarray", "numpy.array", "numpy.ascontiguousarray") \
                    and node.args \
                    and _references_traced(node.args[0], params, ctx.aliases):
                yield ctx.finding(
                    "host-sync/np-on-tracer", node,
                    f"{label} (traced via {how}): {name}() materializes a "
                    f"tracer on host; use jnp.asarray or keep the array "
                    f"device-resident",
                )
