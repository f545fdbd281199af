"""repro-lint: AST rules for the window data plane's implicit contracts.

The relocation pipeline has invariants the type system cannot see —
host effects must stay out of jitted code, collectives must be issued
identically on every rank, ``sync_async`` handles must reach a barrier.
Each rule below is distilled from a bug class an earlier PR actually
hit (or defensively guards against); the linter makes them machine
checked at review time instead of runtime-deadlock time.  Pure stdlib
``ast`` — no third-party dependency.

Rules
-----
RL001  host effect (telemetry span/event, ``time.*``, ``print``/``open``)
       inside a ``@jax.jit``-decorated function or a function traced by
       ``lax.scan``/``while_loop``/``fori_loop``/``cond``/``vmap``/
       ``shard_map``.  Host callbacks silently run once at trace time —
       a span that "measures" a jitted loop measures nothing.
RL002  collective call (``exchange``/``allgather*``/``allreduce*``/
       ``broadcast*``/``barrier``/``sync``/``alltoall``...) inside a
       rank-conditioned branch: the cross-rank drift class that
       PipeBackend's sequence tags only catch at runtime, as a late
       deadlock or tag mismatch.
RL003  ``isinstance(x, DeviceTransport)`` (or any transport class):
       transports are a protocol — test the ``device_plane`` attribute
       so third-party transports behave identically.
RL004  ``sync_async()`` result dropped: a window handle that never
       reaches ``finish()``/``enqueue()``/``drain()`` leaks an
       unfinished relocation (entries extracted, never committed).
RL005  bare ``except:`` — window/steal code paths must never swallow
       ``KeyboardInterrupt``/``SystemExit`` or hide a rollback error.
RL006  ``enumerate(<x>.keys())`` / ``enumerate(<x>.items())`` feeding a
       positional assignment: handle-dict iteration order depends on
       how background deliveries interleaved with admissions — sort
       first (the ``register_drain`` round-robin bug class).
RL007  unused module-level import (dead imports accumulate fast in a
       codebase grown one PR at a time).
RL008  bare ``Connection.recv()`` with no ``poll(timeout)`` anywhere in
       the same scope: a peer that dies mid-collective leaves the
       caller blocked forever (the hang the deadline-aware
       ``PipeBackend._recv`` exists to prevent) — poll with a timeout
       and treat expiry/EOF as peer failure.
RL009  direct ``pl.pallas_call`` outside ``kernels/``: kernels must
       register in ``kernels.ops``'s backend dispatch so the
       interpret-mode CPU fallback and the XLA reference path are
       never bypassed — a raw ``pallas_call`` in data-plane code
       breaks CPU CI silently.

Suppression: add ``# noqa`` (optionally ``# noqa: RL00x``) or
``# repro-lint: ok`` on the flagged line.

CLI: ``python -m repro.analysis.lint <paths> [--format=text|github]``.
Exits 1 when any finding survives, 0 on a clean tree — the CI gate.
"""
from __future__ import annotations

import argparse
import ast
import os
import re
import sys
from dataclasses import dataclass

__all__ = ["Finding", "lint_file", "lint_paths", "lint_source", "main",
           "RULES"]

RULES = {
    "RL001": "host effect inside jit/lax-traced function",
    "RL002": "collective call inside rank-conditioned branch",
    "RL003": "isinstance on a transport class (use the device_plane "
             "protocol attribute)",
    "RL004": "sync_async() result never reaches finish()/enqueue()",
    "RL005": "bare except",
    "RL006": "enumerate over dict-ordered keys()/items() feeding "
             "relocation (sort first)",
    "RL007": "unused module-level import",
    "RL008": "bare Connection.recv() without a poll(timeout) guard in "
             "scope",
    "RL009": "direct pallas_call outside kernels/ (route through the "
             "kernels.ops backend dispatch)",
}

# RL001: names that must not be called from traced code
_HOST_EFFECT_CALLS = {"print", "open", "input", "breakpoint"}
_HOST_EFFECT_ATTRS = {
    # module-qualified: time.time() inside jit measures trace time once
    "time": {"time", "perf_counter", "monotonic", "sleep",
             "process_time"},
    # every telemetry entry point allocates host records
    "telemetry": {"span", "event", "complete", "context", "inc", "gauge",
                  "observe"},
    "obs": {"span", "event", "complete", "context", "inc", "gauge",
            "observe"},
}

# calls whose function-valued arguments are traced by JAX
_TRACING_CALLS = {"jit", "vmap", "pmap", "scan", "while_loop",
                  "fori_loop", "cond", "switch", "map", "shard_map",
                  "checkpoint", "remat", "grad", "value_and_grad"}

# RL002: collective surface of PlaceGroup/backends/managers
_COLLECTIVE_NAMES = {
    "exchange", "alltoall", "allgather", "allgather1", "allgather_spans",
    "allreduce_sum", "allreduce", "broadcast", "broadcast_from",
    "barrier", "sync", "sync_async", "exchange_counts",
    "exchange_range_claims", "update_dist",
}

_TRANSPORT_CLASSES = {"DeviceTransport", "HostTransport",
                      "DistributedTransport", "RelocationTransport"}

# RL007: identifier-shaped words inside string constants (forward-ref
# annotations, __all__ entries) count as usage
_IDENT_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


@dataclass
class Finding:
    path: str
    line: int
    col: int
    code: str
    message: str

    def text(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.code} {self.message}"

    def github(self) -> str:
        # GitHub Actions workflow-command annotation format
        return (f"::error file={self.path},line={self.line},"
                f"col={self.col},title=repro-lint {self.code}::"
                f"{self.message}")


# ---------------------------------------------------------------------------
# small AST helpers
# ---------------------------------------------------------------------------
def _dotted(node) -> str | None:
    """'jax.lax.scan' for an Attribute/Name chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _tail(node) -> str | None:
    """Final attribute/name of a call target ('scan' for jax.lax.scan)."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _is_jit_decorator(dec) -> bool:
    d = _dotted(dec)
    if d in ("jit", "jax.jit"):
        return True
    if isinstance(dec, ast.Call):
        f = _dotted(dec.func)
        if f in ("jit", "jax.jit"):
            return True           # @jax.jit(static_argnums=...)
        if f in ("partial", "functools.partial") and dec.args:
            return _dotted(dec.args[0]) in ("jit", "jax.jit")
    return False


def _add_parents(tree) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._lint_parent = node  # type: ignore[attr-defined]


def _enclosing_function(node):
    cur = getattr(node, "_lint_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return cur
        cur = getattr(cur, "_lint_parent", None)
    return None


# ---------------------------------------------------------------------------
# the checker
# ---------------------------------------------------------------------------
class _FileChecker:
    def __init__(self, path: str, tree: ast.Module, source: str):
        self.path = path
        self.tree = tree
        self.lines = source.splitlines()
        self.findings: list[Finding] = []
        self._seen: set[tuple] = set()
        _add_parents(tree)

    def flag(self, node, code: str, message: str) -> None:
        line = getattr(node, "lineno", 1)
        key = (line, getattr(node, "col_offset", 0), code)
        if key in self._seen:   # nested rank-conditioned ifs etc.
            return
        self._seen.add(key)
        raw = self.lines[line - 1] if 0 < line <= len(self.lines) else ""
        if "repro-lint: ok" in raw:
            return
        if "# noqa" in raw:
            _, _, rest = raw.partition("# noqa")
            # bare `# noqa` suppresses everything on the line;
            # `# noqa: RL004` suppresses only the listed codes
            if not rest.lstrip().startswith(":") or code in rest:
                return
        self.findings.append(Finding(self.path, line,
                                     getattr(node, "col_offset", 0) + 1,
                                     code, message))

    def run(self) -> list[Finding]:
        self.check_traced_host_effects()
        self.check_rank_conditioned_collectives()
        self.check_isinstance_transport()
        self.check_dropped_sync_async()
        self.check_bare_except()
        self.check_dict_order_roundrobin()
        self.check_unused_imports()
        self.check_bare_recv()
        self.check_pallas_call_outside_kernels()
        return self.findings

    # -- RL001 -------------------------------------------------------------
    def _traced_roots(self) -> list[ast.AST]:
        roots: list[ast.AST] = []
        traced_names: set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(_is_jit_decorator(d) for d in node.decorator_list):
                    roots.append(node)
            elif isinstance(node, ast.Call):
                if _tail(node.func) in _TRACING_CALLS:
                    for arg in list(node.args) + [kw.value
                                                  for kw in node.keywords]:
                        if isinstance(arg, ast.Lambda):
                            roots.append(arg)
                        elif isinstance(arg, ast.Name):
                            traced_names.add(arg.id)
        if traced_names:
            for node in ast.walk(self.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                        and node.name in traced_names \
                        and node not in roots:
                    roots.append(node)
        return roots

    def check_traced_host_effects(self) -> None:
        seen: set[int] = set()
        for root in self._traced_roots():
            for node in ast.walk(root):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                f = node.func
                bad = None
                if isinstance(f, ast.Name) and f.id in _HOST_EFFECT_CALLS:
                    bad = f.id
                elif isinstance(f, ast.Attribute):
                    base = _dotted(f.value)
                    if base is not None:
                        mod = base.split(".")[-1]
                        if f.attr in _HOST_EFFECT_ATTRS.get(mod, ()):
                            bad = f"{mod}.{f.attr}"
                if bad is not None:
                    seen.add(id(node))
                    self.flag(node, "RL001",
                              f"host call {bad}() inside a jit/lax-traced "
                              "function runs once at trace time, not per "
                              "step — hoist it out of the traced region")

    # -- RL002 -------------------------------------------------------------
    @staticmethod
    def _rank_conditioned(test) -> bool:
        for node in ast.walk(test):
            if isinstance(node, ast.Attribute) and node.attr == "rank":
                return True
            if isinstance(node, ast.Name) and node.id == "rank":
                return True
            if isinstance(node, ast.Call) \
                    and _tail(node.func) in ("rank_of", "is_local"):
                return True
        return False

    def check_rank_conditioned_collectives(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.If, ast.While)):
                continue
            if not self._rank_conditioned(node.test):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr in _COLLECTIVE_NAMES:
                    # the test itself may call is_local(); skip nodes
                    # inside the test expression
                    cur = sub
                    in_test = False
                    while cur is not None:
                        if cur is node.test:
                            in_test = True
                            break
                        cur = getattr(cur, "_lint_parent", None)
                    if in_test:
                        continue
                    self.flag(sub, "RL002",
                              f"collective .{sub.func.attr}() inside a "
                              "rank-conditioned branch: ranks drift out "
                              "of program order (deadlock or seq-tag "
                              "mismatch) — issue collectives "
                              "unconditionally on every rank")

    # -- RL003 -------------------------------------------------------------
    def check_isinstance_transport(self) -> None:
        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Call)
                    and _tail(node.func) == "isinstance"
                    and len(node.args) == 2):
                continue
            classes = node.args[1]
            names = []
            for sub in ast.walk(classes):
                t = _tail(sub)
                if t in _TRANSPORT_CLASSES:
                    names.append(t)
            if names:
                self.flag(node, "RL003",
                          f"isinstance on transport class "
                          f"{'/'.join(sorted(set(names)))} — transports "
                          "are a protocol; test the `device_plane` "
                          "attribute (or use make_transport) so foreign "
                          "implementations behave identically")

    # -- RL004 -------------------------------------------------------------
    @staticmethod
    def _scope_nodes(fn) -> list[ast.AST]:
        """Nodes of one function (or module) scope, not descending into
        nested defs/lambdas — a handle passed into a nested scope shows
        up here as a Name load, which counts as use."""
        out: list[ast.AST] = []
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            out.append(node)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            stack.extend(ast.iter_child_nodes(node))
        return out

    def check_dropped_sync_async(self) -> None:
        scopes = [n for n in ast.walk(self.tree)
                  if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        scopes.append(self.tree)  # module level
        for fn in scopes:
            body_nodes = self._scope_nodes(fn)
            has_drain = any(isinstance(n, ast.Call)
                            and _tail(n.func) == "drain"
                            for n in body_nodes)
            for node in body_nodes:
                if not (isinstance(node, ast.Call)
                        and _tail(node.func) == "sync_async"):
                    continue
                parent = getattr(node, "_lint_parent", None)
                # chained mm.sync_async(...).finish(): parent is the
                # outer call's Attribute — the handle reaches a barrier
                if isinstance(parent, ast.Attribute):
                    continue
                if isinstance(parent, (ast.Return, ast.Await)):
                    continue
                if isinstance(parent, ast.Expr):
                    if not has_drain:
                        self.flag(node, "RL004",
                                  "sync_async() result dropped and no "
                                  "drain() in scope: the window is never "
                                  "committed — keep the handle and "
                                  "finish()/enqueue() it, or call "
                                  "manager.drain()")
                    continue
                if isinstance(parent, ast.Assign) \
                        and len(parent.targets) == 1 \
                        and isinstance(parent.targets[0], ast.Name):
                    name = parent.targets[0].id
                    used = any(isinstance(n, ast.Name) and n.id == name
                               and isinstance(n.ctx, ast.Load)
                               for n in body_nodes)
                    if not used and not has_drain:
                        self.flag(node, "RL004",
                                  f"sync_async() handle `{name}` is "
                                  "never used: no path reaches "
                                  "finish()/enqueue(), the window is "
                                  "never committed")

    # -- RL005 -------------------------------------------------------------
    def check_bare_except(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                self.flag(node, "RL005",
                          "bare `except:` swallows KeyboardInterrupt/"
                          "SystemExit and hides rollback errors — catch "
                          "Exception (or BaseException and re-raise)")

    # -- RL006 -------------------------------------------------------------
    def check_dict_order_roundrobin(self) -> None:
        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Call)
                    and _tail(node.func) == "enumerate" and node.args):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Call) \
                    and _tail(arg.func) in ("keys", "items"):
                self.flag(node, "RL006",
                          f"enumerate over .{_tail(arg.func)}(): handle-"
                          "dict order depends on how background "
                          "deliveries interleaved with admissions — "
                          "sort the keys first so positional assignment "
                          "(round-robin destinations) is deterministic")

    # -- RL007 -------------------------------------------------------------
    def check_unused_imports(self) -> None:
        if os.path.basename(self.path) == "__init__.py":
            return  # re-export hubs import for the namespace
        bound: list[tuple[str, ast.AST]] = []
        for node in self.tree.body:
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name = alias.asname or alias.name.split(".")[0]
                    bound.append((name, node))
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    bound.append((alias.asname or alias.name, node))
        if not bound:
            return
        used: set[str] = set()
        import_nodes = {id(n) for _, n in bound}
        for node in ast.walk(self.tree):
            if id(node) in import_nodes:
                continue
            if isinstance(node, ast.Name):
                used.add(node.id)
            elif isinstance(node, ast.Constant) \
                    and isinstance(node.value, str) \
                    and len(node.value) < 200:
                # identifiers inside short string constants count as
                # usage: __all__ entries and forward-reference / string
                # annotations ('dests: "Sequence[int]"') resolve the
                # name at get_type_hints time even though no Name node
                # loads it
                used.update(_IDENT_RE.findall(node.value))
        for name, node in bound:
            if name not in used:
                self.flag(node, "RL007",
                          f"`{name}` is imported but never used")

    # -- RL008 -------------------------------------------------------------
    def check_bare_recv(self) -> None:
        """Flag ``<x>.recv()`` calls in any scope that never calls
        ``<y>.poll(<timeout>)``: with nothing bounding the wait, a dead
        peer blocks the caller forever.  Scope-level, not dataflow —
        one guarded poll in the function is taken as evidence the
        author bounded the wait (the ``PipeBackend._recv`` pattern)."""
        scopes = [n for n in ast.walk(self.tree)
                  if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        scopes.append(self.tree)  # module level
        for fn in scopes:
            body_nodes = self._scope_nodes(fn)
            has_poll = any(
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "poll"
                and (n.args or n.keywords)
                for n in body_nodes)
            if has_poll:
                continue
            for node in body_nodes:
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "recv" \
                        and not node.args and not node.keywords:
                    self.flag(node, "RL008",
                              "bare .recv() with no poll(timeout) in "
                              "scope: a dead peer blocks this call "
                              "forever — poll with a deadline first and "
                              "treat expiry/EOF as peer failure")

    # -- RL009 -------------------------------------------------------------
    def check_pallas_call_outside_kernels(self) -> None:
        """Flag any ``pallas_call`` invocation in a file that does not
        live under a ``kernels`` directory: everything outside the
        kernel library must go through ``kernels.ops``, whose dispatch
        is what keeps the interpret-mode CPU fallback and the XLA
        reference path selectable (``set_backend``/
        ``REPRO_KERNEL_BACKEND``)."""
        parts = os.path.normpath(self.path).split(os.sep)
        if "kernels" in parts:
            return  # the kernel library itself is the one allowed home
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) \
                    and _tail(node.func) == "pallas_call":
                self.flag(node, "RL009",
                          "direct pallas_call outside kernels/: this "
                          "kernel bypasses the kernels.ops backend "
                          "dispatch, so interpret-mode CPU CI and the "
                          "XLA reference path never see it — move it "
                          "into kernels/ and register it in ops")


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------
def lint_source(source: str, path: str = "<string>",
                select: set[str] | None = None) -> list[Finding]:
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 1, (e.offset or 0) + 1, "RL000",
                        f"syntax error: {e.msg}")]
    findings = _FileChecker(path, tree, source).run()
    if select:
        findings = [f for f in findings if f.code in select]
    findings.sort(key=lambda f: (f.line, f.col, f.code))
    return findings


def lint_file(path: str, select: set[str] | None = None) -> list[Finding]:
    with open(path, encoding="utf-8") as f:
        return lint_source(f.read(), path, select)


def _iter_py_files(paths):
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                for name in sorted(files):
                    if name.endswith(".py"):
                        yield os.path.join(root, name)


def lint_paths(paths, select: set[str] | None = None) -> list[Finding]:
    findings: list[Finding] = []
    for path in _iter_py_files(paths):
        findings.extend(lint_file(path, select))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro-lint",
        description="static contract checks for the relocation data plane")
    ap.add_argument("paths", nargs="*", default=["src"],
                    help="files or directories to lint (default: src)")
    ap.add_argument("--format", choices=("text", "github"), default="text",
                    help="'github' emits Actions error annotations")
    ap.add_argument("--select", default=None,
                    help="comma-separated rule codes (default: all)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)
    if args.list_rules:
        for code, desc in sorted(RULES.items()):
            print(f"{code}  {desc}")
        return 0
    select = set(args.select.split(",")) if args.select else None
    findings = lint_paths(args.paths or ["src"], select)
    for f in findings:
        print(f.github() if args.format == "github" else f.text())
    if findings:
        print(f"repro-lint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
