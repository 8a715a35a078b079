"""cache-invalidation: a write to a table's routing state moves its token.

The broker routes every query from a *route snapshot* of the table that it
holds (`cluster/routing.py` `RouteSnapshot`: configs, schema, segment
metadata, ideal states, external views, server instances) and asks the controller once a
query whether the snapshot's token still stands; its result and plan caches
(cluster/result_cache.py) key on the same token. The token is made of counter
documents: a table's routing version and the instances' version. A write to
anything the snapshot holds that leaves its counter where it was is a
silent-staleness bug: the broker is told "unchanged", routes on the old
state and serves cached rows of it forever — no error, no metric, just wrong
rows.

Rule: a PropertyStore write — a `*.store.set(...)` / `.update(...)` /
`.delete(...)` call, receiver named `store` or `*_store` — whose argument
tree carries a string constant containing `idealstate`, `externalview`, `/segments/`,
`/config`, `/schemas/` or `/instances/` must name the counter it moves as
`bump=` **of that call**: the store then writes and counts in one section, so
no reader sees the one without the other. A `bump_routing_version(...)` call
somewhere else in the function does not do: it leaves a window between the
write and the count. Detection is syntactic, in the
atomic-write mold: path strings assembled in a separate statement escape the
net, and a write that needs no count carries a reasoned
`# pinotlint: disable=cache-invalidation — <why>`.

Exempt: cluster/metadata.py (the store itself).
"""

from __future__ import annotations

import ast

from pinot_tpu.devtools.lint.core import Checker, Finding, ModuleInfo

#: path substrings that mark a store write as one to what a route snapshot holds
_MUTATION_MARKERS = ("idealstate", "externalview", "/segments/", "/config", "/schemas/", "/instances/")


def _mutation_marker_in(node: ast.AST) -> str | None:
    for c in ast.walk(node):
        if isinstance(c, ast.Constant) and isinstance(c.value, str):
            for m in _MUTATION_MARKERS:
                if m in c.value:
                    return m
    return None


def _is_store_write(node: ast.Call) -> bool:
    """`<expr>.store.set(...)`/`.update(...)`/`.delete(...)` or a bare
    `store.set(...)` — receiver must END in `store` so e.g.
    `self.caches.result.set` never matches."""
    f = node.func
    if not (isinstance(f, ast.Attribute) and f.attr in ("set", "update", "delete")):
        return False
    recv = f.value
    if isinstance(recv, ast.Attribute):
        return recv.attr == "store" or recv.attr.endswith("_store")
    if isinstance(recv, ast.Name):
        return recv.id == "store" or recv.id.endswith("_store")
    return False


def _names_its_counter(node: ast.Call) -> bool:
    return any(kw.arg == "bump" for kw in node.keywords)


class CacheInvalidationChecker(Checker):
    name = "cache-invalidation"

    def check_module(self, module: ModuleInfo) -> list[Finding]:
        p = module.path.replace("\\", "/")
        if p.endswith("cluster/metadata.py"):
            return []  # the PropertyStore itself
        found: dict[int, Finding] = {}  # by line: a nested function's write is named for the innermost
        for fn in ast.walk(module.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call) and _is_store_write(node)) or _names_its_counter(node):
                    continue
                marker = _mutation_marker_in(node)
                if marker:
                    found[node.lineno] = Finding(
                        self.name,
                        module.path,
                        node.lineno,
                        f"routing-state write ({marker!r} store write) in {fn.name}() without "
                        "`bump=`: the broker's route snapshot and its result/plan caches key on "
                        "the counter and will route and serve on stale state forever",
                    )
        return [found[line] for line in sorted(found)]
