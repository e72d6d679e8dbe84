#!/usr/bin/env python
"""graft-analyze: TPU tracing-safety & concurrency static analyzer.

The role of the reference's static gate (ci/check_style.sh +
cpp/scripts/include_checker.py), retargeted at the failure modes that
actually bite a TPU serving stack: host syncs and retraces on hot
paths, collectives against unbound mesh axes, index mutations that skip
their epoch bump (stale ResultCache hits forever), scheduler state
touched outside its lock, and re-typed merge-padding sentinels.  Effort
goes where the invariants are load-bearing (the EQuARX philosophy —
arXiv:2506.17615 — applied to analysis instead of bandwidth).

Checks
======

style           tabs / trailing whitespace / EOF newline / wildcard
                imports / syntax (the absorbed ci/check_style gate).
cite            raft_tpu library modules carry a reference citation
                ("Ref:") in the module docstring.
host-sync       from every jitted / shard_map'ped entry point (ops/,
                parallel/, comms/, serve/ and anywhere else in
                raft_tpu), walk the call graph and flag numpy calls,
                float()/int()/bool(), .item()/.tolist() and Python
                if/while branching on traced values — each one is a
                ConcretizationError or a silent retrace-per-value.
                Outside traced code, flag device->host->device round
                trips (an np.asarray on a device array whose result
                feeds back into jnp) — a mid-pipeline sync.
axis-name       ppermute/psum/pmax/axis_index/... must run under an
                enclosing shard_map/pmap wrapper (reachability over the
                call graph), and literal axis names must be bound
                somewhere in the tree (an unbound name fails only when
                the collective is traced).
epoch-bump      any function mutating index storage (data / indices /
                list_sizes / pq_codes / _db / the lifecycle tombstone
                mask ``deleted``, incl. setattr) must bump an
                ``.epoch`` counter on every return path after the
                mutation — or ResultCache serves stale answers.
                Tombstone-mask writes and list_sizes rewrites count
                because they change which rows answer queries exactly
                like a row write does.
lock-discipline classes owning a threading.Lock may touch their
                container state (queue, dicts, deques) only inside
                ``with self._lock`` — a static race detector for the
                threaded serving subsystem.  Private helpers whose
                intra-class call sites are all lock-held are accepted.
sentinel        merge/padding sentinels (±inf distances, -1 ids) in the
                merge-path modules must come from
                raft_tpu/core/sentinels.py, never re-typed literals.
wall-clock      serve/ and lifecycle/ logic must read the INJECTED
                clock, never call time.time()/time.monotonic()/
                time.perf_counter()/time.sleep() directly — wall time
                in a scheduling or health decision makes replay
                nondeterministic and unfakeable in tests (the
                injectable-clock discipline every serving subsystem
                documents).  Referencing ``time.monotonic`` as a
                DEFAULT (no call) stays legal — that is the injection
                point itself.
recompile-risk  outside traced code, an array extent must not derive
                from a device value materialized to a host int
                (``cap = int(jnp.max(counts))`` feeding
                ``jnp.zeros((n, cap))``): every distinct value bakes a
                fresh shape and recompiles every downstream jit
                consumer.  Pow2 bucketing (``next_pow2``/
                ``.bit_length()``) bounds the class count and is
                accepted; ``.shape``-derived extents are static.
                Inside traced code the same pull is host-sync's domain.

Incremental cache
=================

Results are memoized under ``<root>/.analyze_cache`` in two tiers:
``mod-<hash>.json`` holds one module's local-check results
(style/cite/epoch-bump/lock-discipline/sentinel/wall-clock) keyed by the module's
content, and ``graph-<hash>.json`` holds the whole-program checks
(host-sync/axis-name/recompile-risk) keyed by every module's content —
an interprocedural finding may move when ANY module changes, so the
graph tier is all-or-nothing.  Both keys fold in a fingerprint of the
analyzer's own sources, so editing the analyzer invalidates everything.
The cache is pure memoization: a warm run returns bit-identical
findings (tests/test_analyze_cache.py proves parity), corrupt entries
are re-analyzed, and the directory self-prunes.  ``--no-cache``
bypasses it.

Waivers
=======

Findings are silenced in-line, next to the code they excuse::

    keep = np.asarray(flags)   # analyze: host-sync-ok (boundary pull)

A waiver comment covers its own line and, when it is a comment-only
line, the line below it.  Several checks may be waived at once
(``# analyze: host-sync-ok sentinel-ok — reason``).  There is no
central exemption table: exemptions live with the code.

Usage
=====

    python ci/analyze.py                  # whole tree, all checks, cached
    python ci/analyze.py --check host-sync --check sentinel
    python ci/analyze.py --no-cache --stats --show-waived
    python ci/analyze.py --list-checks

Exit code 0 = clean, 1 = findings (printed one per line).  ``--stats``
adds a cache/waiver summary line; ``--show-waived`` prints the waived
findings (informational, never affect the exit code).
"""

from __future__ import annotations

import argparse
import ast
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

ROOT = Path(__file__).resolve().parent.parent
SCAN = ["raft_tpu", "pylibraft", "raft_dask", "tests", "bench", "ci"]

CHECKS = ("style", "cite", "host-sync", "axis-name", "epoch-bump",
          "lock-discipline", "sentinel", "recompile-risk", "wall-clock")

# Cache tiers: a LOCAL check reads one module in isolation, so its
# results key on that module's content alone; a GRAPH check walks the
# interprocedural call graph, so its results key on every module.
LOCAL_CHECKS = ("style", "cite", "epoch-bump", "lock-discipline",
                "sentinel", "wall-clock")
GRAPH_CHECKS = ("host-sync", "axis-name", "recompile-risk")

# Semantic findings are emitted for the library tree only (the whole
# tree still feeds the call graph, so tests/bench wrappers count for
# reachability).
SEMANTIC_SCOPE = "raft_tpu/"

# Injected-clock discipline scope: serving/lifecycle decision logic
# must read the clock it was constructed with, never wall time — a
# wall-clock read makes shed/hedge/degrade decisions unreplayable and
# untestable (tests drive these subsystems tick by tick).
WALL_CLOCK_SCOPE = ("raft_tpu/serve/", "raft_tpu/lifecycle/")
WALL_CLOCK_CALLS = {"time.time", "time.monotonic", "time.perf_counter",
                    "time.sleep"}

# The one allowed home of merge/pad sentinel literals ...
SENTINEL_HOME = "raft_tpu/core/sentinels.py"
# ... enforced over the merge-path modules.
SENTINEL_SCOPE = (
    "raft_tpu/comms/",
    "raft_tpu/parallel/",
    "raft_tpu/serve/",
    "raft_tpu/lifecycle/",
    "raft_tpu/obs/",
    "raft_tpu/neighbors/brute_force.py",
    "raft_tpu/matrix/select_k.py",
)

# Index-content mutations that must bump .epoch on every return path.
# "deleted" is the lifecycle tombstone mask (a mask write changes which
# rows answer queries exactly like a row write); compaction publishes
# construct a NEW index (copy-on-write) so they carry the bump in the
# constructor instead of tripping this set.
STORAGE_ATTRS = {"data", "indices", "list_sizes", "pq_codes", "_db",
                 "deleted"}
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize", "sharding",
                "weak_type", "nbytes"}
SYNC_METHODS = {"item", "tolist", "block_until_ready"}
COLLECTIVES = {"psum", "pmin", "pmax", "pmean", "ppermute", "all_gather",
               "all_to_all", "psum_scatter", "axis_index", "axis_size"}
# axis-name argument position per collective (fallback: keyword axis_name).
COLLECTIVE_AXIS_POS = {"axis_index": 0, "axis_size": 0}
WRAPPER_NAMES = {"shard_map", "pmap"}
# jax higher-order controls whose callback arguments trace with all
# params traced: name -> callback argument positions.
HOF_CALLBACKS = {"scan": (0,), "while_loop": (0, 1), "fori_loop": (2,),
                 "cond": (1, 2), "map": (0,), "vmap": (0,),
                 "checkpoint": (0,), "remat": (0,)}
CONTAINER_CTORS = {"list", "dict", "set", "deque", "OrderedDict",
                   "defaultdict"}
# recompile-risk: jax constructors whose first argument is a shape.
SHAPE_CTORS = {"zeros", "ones", "full", "empty"}
# Bucketing sanitizers: pow2 rounding bounds the capacity-class count
# to log-many, the deliberate design of serve/bucketing — extents
# laundered through these do NOT count as data-dependent.
BUCKET_FNS = {"next_pow2"}
BUCKET_METHODS = {"bit_length"}
CAST_BUILTINS = {"float", "int", "bool"}
SAFE_BUILTINS = {"len", "isinstance", "range", "type", "repr", "str",
                 "print", "format", "hasattr", "id", "sorted", "zip",
                 "enumerate"}

WAIVE_LINE_RE = re.compile(r"#\s*analyze:\s*(.+)$")
WAIVE_TOKEN_RE = re.compile(r"([a-z][a-z0-9-]*)-ok\b")


@dataclass(frozen=True)
class Finding:
    rel: str
    line: int
    check: str
    msg: str

    def render(self) -> str:
        return f"{self.rel}:{self.line}: [{self.check}] {self.msg}"


@dataclass
class FuncInfo:
    qual: str
    name: str
    module: "ModuleInfo"
    node: ast.AST                      # FunctionDef / AsyncFunctionDef / Lambda
    params: List[str]
    parent: Optional["FuncInfo"] = None
    cls: Optional[str] = None
    nested: Dict[str, "FuncInfo"] = field(default_factory=dict)
    jit_static: Optional[Set[str]] = None    # set => jit entry point

    @property
    def line(self) -> int:
        return self.node.lineno

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


@dataclass
class ModuleInfo:
    rel: str
    name: str
    tree: ast.Module
    lines: List[str]
    imports: Dict[str, str] = field(default_factory=dict)
    top: Dict[str, FuncInfo] = field(default_factory=dict)
    funcs: List[FuncInfo] = field(default_factory=list)
    waivers: Dict[int, Set[str]] = field(default_factory=dict)


# ---------------------------------------------------------------------------
# Parsing / collection


def _params_of(args: ast.arguments) -> List[str]:
    names = [a.arg for a in args.posonlyargs] + [a.arg for a in args.args]
    if args.vararg:
        names.append(args.vararg.arg)
    names += [a.arg for a in args.kwonlyargs]
    if args.kwarg:
        names.append(args.kwarg.arg)
    return names


def _const_strs(node) -> List[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
        return out
    return []


def _const_ints(node) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)]
    return []


class _Collector(ast.NodeVisitor):
    """One pass per module: imports, function/class structure, waivers."""

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.fn_stack: List[FuncInfo] = []
        self.cls_stack: List[str] = []

    # -- imports (collected at any nesting level) --------------------------
    def visit_Import(self, node: ast.Import):
        for a in node.names:
            self.mod.imports[a.asname or a.name.split(".")[0]] = (
                a.name if a.asname else a.name.split(".")[0])
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        base = node.module or ""
        if node.level:  # relative: anchor at this module's package
            pkg = self.mod.name.split(".")
            pkg = pkg[: len(pkg) - node.level]
            base = ".".join(pkg + ([node.module] if node.module else []))
        for a in node.names:
            if a.name == "*":
                continue
            self.mod.imports[a.asname or a.name] = f"{base}.{a.name}"
        self.generic_visit(node)

    # -- functions ---------------------------------------------------------
    def _register(self, node, name: str) -> FuncInfo:
        parent = self.fn_stack[-1] if self.fn_stack else None
        cls = self.cls_stack[-1] if (self.cls_stack and parent is None) \
            else None
        qual = f"{self.mod.name}::" + ".".join(
            [f.name for f in self.fn_stack] + [name])
        fi = FuncInfo(qual=qual, name=name, module=self.mod, node=node,
                      params=_params_of(node.args), parent=parent, cls=cls)
        self.mod.funcs.append(fi)
        if parent is not None:
            parent.nested[name] = fi
        elif cls is None:
            self.mod.top[name] = fi
        return fi

    def _jit_static(self, fi: FuncInfo, deco_list) -> None:
        for d in deco_list:
            dotted = _dotted_expr(d if not isinstance(d, ast.Call) else
                                  d.func)
            call = d if isinstance(d, ast.Call) else None
            if call is not None and dotted and dotted.endswith("partial"):
                if not call.args:
                    continue
                inner = _dotted_expr(call.args[0])
                if not inner or not inner.split(".")[-1] == "jit":
                    continue
            elif not dotted or dotted.split(".")[-1] != "jit":
                continue
            static: Set[str] = set()
            if call is not None:
                for kw in call.keywords:
                    if kw.arg == "static_argnames":
                        static |= set(_const_strs(kw.value))
                    elif kw.arg == "static_argnums":
                        pos_params = ([a.arg for a in fi.node.args.posonlyargs]
                                      + [a.arg for a in fi.node.args.args])
                        for i in _const_ints(kw.value):
                            if 0 <= i < len(pos_params):
                                static.add(pos_params[i])
            fi.jit_static = static
            return

    def visit_FunctionDef(self, node):
        fi = self._register(node, node.name)
        self._jit_static(fi, node.decorator_list)
        self.fn_stack.append(fi)
        self.cls_stack.append("")  # nested classes don't make methods
        for stmt in node.body:
            self.visit(stmt)
        self.cls_stack.pop()
        self.fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        fi = self._register(node, f"<lambda:{node.lineno}>")
        self.fn_stack.append(fi)
        self.visit(node.body)
        self.fn_stack.pop()

    def visit_ClassDef(self, node):
        self.cls_stack.append(node.name)
        for stmt in node.body:
            self.visit(stmt)
        self.cls_stack.pop()


def _dotted_expr(e) -> Optional[str]:
    """'a.b.c' for a pure attribute chain rooted at a Name, else None."""
    parts = []
    while isinstance(e, ast.Attribute):
        parts.append(e.attr)
        e = e.value
    if isinstance(e, ast.Name):
        parts.append(e.id)
        return ".".join(reversed(parts))
    return None


def _collect_waivers(lines: List[str]) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    for ln, line in enumerate(lines, 1):
        m = WAIVE_LINE_RE.search(line)
        if m:
            toks = set(WAIVE_TOKEN_RE.findall(m.group(1)))
            if toks:
                out[ln] = toks
    return out


# ---------------------------------------------------------------------------
# Analyzer


class Analyzer:
    def __init__(self, files: Dict[str, str]):
        self.modules: Dict[str, ModuleInfo] = {}
        self.findings: List[Finding] = []
        self._seen: Set[Tuple] = set()
        self.methods: Dict[str, List[FuncInfo]] = {}
        self.parse_errors: List[Finding] = []
        for rel, text in sorted(files.items()):
            self._load(rel, text)
        for mod in self.modules.values():
            for fi in mod.funcs:
                if fi.cls is not None:
                    self.methods.setdefault(fi.name, []).append(fi)
        self.traced: Set[FuncInfo] = set()
        self.wrapped: Set[FuncInfo] = set()
        self.traced_params: Dict[FuncInfo, Set[str]] = {}
        self._files = files
        self.waived: List[Finding] = []
        self._seen_waived: Set[Tuple] = set()
        self._graph_built = False

    def _load(self, rel: str, text: str) -> None:
        try:
            tree = ast.parse(text)
        except SyntaxError as e:
            self.parse_errors.append(Finding(
                rel, e.lineno or 1, "style", f"syntax error: {e.msg}"))
            return
        name = rel[:-3].replace("/", ".")
        if name.endswith(".__init__"):
            name = name[: -len(".__init__")]
        lines = text.split("\n")
        mod = ModuleInfo(rel=rel, name=name, tree=tree, lines=lines,
                         waivers=_collect_waivers(lines))
        _Collector(mod).visit(tree)
        self.modules[name] = mod

    # -- reporting ---------------------------------------------------------
    def report(self, mod: ModuleInfo, line: int, check: str,
               msg: str) -> None:
        waived = set(mod.waivers.get(line, ()))
        prev = mod.waivers.get(line - 1)
        if prev and line - 2 < len(mod.lines) and \
                mod.lines[line - 2].lstrip().startswith("#"):
            waived |= prev
        key = (mod.rel, line, check, msg)
        if check in waived:
            # Waived findings are recorded (cache / --show-waived /
            # --stats surface them) but never affect the exit code.
            # Deduped per site — one waiver comment, one record, even
            # when several return paths would re-derive the finding.
            wkey = (mod.rel, line, check)
            if wkey not in self._seen_waived:
                self._seen_waived.add(wkey)
                self.waived.append(Finding(mod.rel, line, check, msg))
            return
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(Finding(mod.rel, line, check, msg))

    # -- resolution --------------------------------------------------------
    def _resolve_dotted(self, dotted: str):
        """A dotted path to a scanned function, scanned module, or ext."""
        parts = dotted.split(".")
        for cut in range(len(parts), 0, -1):
            prefix = ".".join(parts[:cut])
            mod = self.modules.get(prefix)
            if mod is None:
                continue
            rest = parts[cut:]
            if not rest:
                return ("module", mod)
            if len(rest) == 1 and rest[0] in mod.top:
                return ("func", mod.top[rest[0]])
            return ("ext", dotted)
        return ("ext", dotted)

    def resolve_name(self, name: str, func: Optional[FuncInfo],
                     mod: ModuleInfo):
        f = func
        while f is not None:
            if name in f.nested:
                return ("func", f.nested[name])
            if name in f.params:
                return ("param", name)
            f = f.parent
        if name in mod.top:
            return ("func", mod.top[name])
        if name in mod.imports:
            return self._resolve_dotted(mod.imports[name])
        return ("ext", f"builtins.{name}")

    def call_targets(self, fn_expr, func: Optional[FuncInfo],
                     mod: ModuleInfo) -> List[Tuple[str, object]]:
        """Resolutions of a call's callee: [("func", FuncInfo)] /
        [("ext", dotted)] / method candidates / [("param", name)]."""
        if isinstance(fn_expr, ast.Name):
            r = self.resolve_name(fn_expr.id, func, mod)
            return [r] if r else []
        if isinstance(fn_expr, ast.Attribute):
            dotted = _dotted_expr(fn_expr)
            if dotted:
                root = dotted.split(".")[0]
                res = self.resolve_name(root, func, mod)
                if res and res[0] == "ext" and \
                        res[1] != f"builtins.{root}":
                    tail = dotted[len(root):]
                    return [self._resolve_dotted(res[1] + tail)]
                if res and res[0] == "module":
                    tail = dotted[len(root):]
                    return [self._resolve_dotted(res[1].name + tail)]
                if res and res[0] == "ext":
                    # unresolved bare root: fall through to methods
                    pass
            cands = self.methods.get(fn_expr.attr, [])
            return [("func", c) for c in cands]
        return []

    def _ext_of(self, targets) -> Optional[str]:
        for kind, t in targets:
            if kind == "ext":
                return t
        return None

    # -- wrapper bodies / traced set --------------------------------------
    def _callback_funcinfo(self, arg, func, mod) -> Optional[FuncInfo]:
        if isinstance(arg, ast.Lambda):
            f = func
            while f is not None:
                for fi in f.nested.values():
                    if fi.node is arg:
                        return fi
                f = f.parent
            for fi in mod.funcs:
                if fi.node is arg:
                    return fi
            return None
        if isinstance(arg, ast.Name):
            r = self.resolve_name(arg.id, func, mod)
            if r and r[0] == "func":
                return r[1]
            return None
        if isinstance(arg, ast.Call):
            # factory(...) returning a nested def ("return step" pattern)
            for kind, t in self.call_targets(arg.func, func, mod):
                if kind != "func" or isinstance(t.node, ast.Lambda):
                    continue
                body = t.node.body
                if body and isinstance(body[-1], ast.Return) and \
                        isinstance(body[-1].value, ast.Name):
                    inner = t.nested.get(body[-1].value.id)
                    if inner is not None:
                        return inner
        return None

    def _iter_calls(self, fi: FuncInfo):
        """Calls lexically inside ``fi`` (not inside nested defs)."""
        body = fi.node.body if not isinstance(fi.node, ast.Lambda) \
            else [fi.node.body]
        stack = list(body)
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            if isinstance(n, ast.Call):
                yield n
            if isinstance(n, ast.AST):
                stack.extend(ast.iter_child_nodes(n))
            elif isinstance(n, list):
                stack.extend(n)

    def build_graph(self) -> None:
        """Wrapper bodies (shard_map/pmap, incl. forwarders), HOF
        callbacks, the traced set and the wrapped-reachable set.
        Idempotent — ``run()`` may be invoked once for the local tier
        and once for the graph tier without rebuilding."""
        if self._graph_built:
            return
        self._graph_built = True
        bodies: Set[FuncInfo] = set()
        hof: Set[FuncInfo] = set()
        forwarders: Dict[FuncInfo, Set[str]] = {}

        changed = True
        while changed:
            changed = False
            for mod in self.modules.values():
                for fi in mod.funcs:
                    for call in self._iter_calls(fi):
                        tgts = self.call_targets(call.func, fi, mod)
                        ext = self._ext_of(tgts)
                        is_wrapper = any(
                            (k == "func" and t.name in WRAPPER_NAMES)
                            for k, t in tgts) or (
                            ext and ext.split(".")[-1] in WRAPPER_NAMES)
                        fwd_params = set()
                        for k, t in tgts:
                            if k == "func" and t in forwarders:
                                fwd_params |= forwarders[t]
                        cb_args = []
                        if is_wrapper and call.args:
                            cb_args.append(call.args[0])
                        if fwd_params:
                            bound = self._bind(tgts, call)
                            for k, t in tgts:
                                if k == "func" and t in forwarders:
                                    for p in forwarders[t]:
                                        if p in bound:
                                            cb_args.append(bound[p])
                        if ext and ext.startswith("jax"):
                            name = ext.split(".")[-1]
                            for pos in HOF_CALLBACKS.get(name, ()):
                                if pos < len(call.args):
                                    cb = self._callback_funcinfo(
                                        call.args[pos], fi, mod)
                                    if cb is not None and cb not in hof:
                                        hof.add(cb)
                                        changed = True
                        for arg in cb_args:
                            if isinstance(arg, ast.Name):
                                r = self.resolve_name(arg.id, fi, mod)
                                if r and r[0] == "param":
                                    if r[1] not in forwarders.setdefault(
                                            fi, set()):
                                        forwarders[fi].add(r[1])
                                        changed = True
                                    continue
                            cb = self._callback_funcinfo(arg, fi, mod)
                            if cb is not None and cb not in bodies:
                                bodies.add(cb)
                                changed = True

        self.wrapper_bodies = bodies
        seeds = set(bodies) | set(hof)
        for mod in self.modules.values():
            for fi in mod.funcs:
                if fi.jit_static is not None:
                    seeds.add(fi)

        # traced set: closure over call edges
        traced = set(seeds)
        queue = list(seeds)
        while queue:
            fi = queue.pop()
            for call in self._iter_calls(fi):
                for k, t in self.call_targets(call.func, fi, fi.module):
                    if k == "func" and t not in traced:
                        traced.add(t)
                        queue.append(t)
        self.traced = traced

        # wrapped-reachable set (axis-name check): closure from bodies
        # over call edges AND lexical nesting (a def inside a shard_map
        # body runs with the same axes bound).
        wrapped = set(bodies)
        queue = list(bodies)
        while queue:
            fi = queue.pop()
            for nfi in fi.nested.values():
                if nfi not in wrapped:
                    wrapped.add(nfi)
                    queue.append(nfi)
            for call in self._iter_calls(fi):
                for k, t in self.call_targets(call.func, fi, fi.module):
                    if k == "func" and t not in wrapped:
                        wrapped.add(t)
                        queue.append(t)
        self.wrapped = wrapped

        # seed traced params
        self.traced_params = {}
        for fi in seeds:
            if fi.jit_static is not None:
                p = [x for x in fi.params
                     if x not in fi.jit_static and x != "self"]
            else:
                p = [x for x in fi.params if x != "self"]
            self.traced_params[fi] = set(p)

    def _bind(self, tgts, call) -> Dict[str, ast.AST]:
        """param name -> arg expression, for the first func target."""
        for k, t in tgts:
            if k != "func":
                continue
            params = t.params
            if t.cls is not None and params and params[0] == "self":
                params = params[1:]
            bound: Dict[str, ast.AST] = {}
            for i, a in enumerate(call.args):
                if isinstance(a, ast.Starred):
                    break
                if i < len(params):
                    bound[params[i]] = a
            for kw in call.keywords:
                if kw.arg:
                    bound[kw.arg] = kw.value
            return bound
        return {}

    # -- host-sync: traced context ----------------------------------------
    def run_host_sync(self) -> None:
        # interprocedural taint fixpoint
        queue = list(self.traced_params)
        rounds = 0
        while queue and rounds < 20000:
            rounds += 1
            fi = queue.pop()
            tainted = self._fn_taint(fi, flag=False)
            for call in self._iter_calls(fi):
                tgts = self.call_targets(call.func, fi, fi.module)
                bound = self._bind(tgts, call)
                for k, t in tgts:
                    if k != "func" or t not in self.traced:
                        continue
                    cur = self.traced_params.setdefault(t, set())
                    new = {p for p, a in bound.items()
                           if self._expr_taint(a, tainted, fi) and
                           p not in cur}
                    if new:
                        cur |= new
                        queue.append(t)
            # closure taint into nested traced functions
            for nfi in fi.nested.values():
                if nfi not in self.traced:
                    continue
                free = {n.id for n in ast.walk(nfi.node)
                        if isinstance(n, ast.Name)}
                cur = self.traced_params.setdefault(nfi, set())
                new = (free & tainted) - set(nfi.params) - cur
                if new:
                    cur |= new
                    queue.append(nfi)
        # flag pass
        for fi in sorted(self.traced, key=lambda f: (f.module.rel, f.line)):
            if not fi.module.rel.startswith(SEMANTIC_SCOPE):
                continue
            self._fn_taint(fi, flag=True)

    def _expr_taint(self, e, tainted: Set[str], fi: FuncInfo) -> bool:
        if isinstance(e, ast.Name):
            return e.id in tainted
        if isinstance(e, ast.Constant):
            return False
        if isinstance(e, ast.Attribute):
            if e.attr in STATIC_ATTRS:
                return False
            return self._expr_taint(e.value, tainted, fi)
        if isinstance(e, ast.Call):
            tgts = self.call_targets(e.func, fi, fi.module)
            ext = self._ext_of(tgts)
            if ext:
                leaf = ext.split(".")[-1]
                if ext.startswith("numpy"):
                    return False        # host result (flagged separately)
                if ext.startswith("builtins.") and (
                        leaf in CAST_BUILTINS or leaf in SAFE_BUILTINS):
                    return False
                if ext.startswith("jax") and leaf == "axis_index":
                    return True
            args = list(e.args) + [kw.value for kw in e.keywords]
            return any(self._expr_taint(a, tainted, fi) for a in args)
        if isinstance(e, ast.Lambda):
            return False
        if isinstance(e, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in e.ops):
            # identity tests (x is None) are Python-level, never traced
            return False
        if isinstance(e, ast.AST):
            return any(self._expr_taint(c, tainted, fi)
                       for c in ast.iter_child_nodes(e)
                       if isinstance(c, ast.AST))
        return False

    def _fn_taint(self, fi: FuncInfo, flag: bool) -> Set[str]:
        tainted = set(self.traced_params.get(fi, ()))
        body = fi.node.body if not isinstance(fi.node, ast.Lambda) \
            else [ast.Expr(value=fi.node.body)]
        for _ in range(3):   # small fixpoint for loop-carried taint
            before = len(tainted)
            self._taint_stmts(body, tainted, fi)
            if len(tainted) == before:
                break
        if flag:
            self._flag_stmts(body, tainted, fi)
        return tainted

    def _taint_targets(self, target, tainted: Set[str]) -> None:
        for n in ast.walk(target):
            if isinstance(n, ast.Name):
                tainted.add(n.id)

    def _taint_stmts(self, stmts, tainted: Set[str], fi: FuncInfo) -> None:
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            if isinstance(s, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = getattr(s, "value", None)
                if value is not None and \
                        self._expr_taint(value, tainted, fi):
                    targets = s.targets if isinstance(s, ast.Assign) \
                        else [s.target]
                    for t in targets:
                        self._taint_targets(t, tainted)
                continue
            if isinstance(s, ast.For):
                if self._expr_taint(s.iter, tainted, fi):
                    self._taint_targets(s.target, tainted)
                self._taint_stmts(s.body, tainted, fi)
                self._taint_stmts(s.orelse, tainted, fi)
                continue
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(s, attr, None)
                if sub:
                    self._taint_stmts(sub, tainted, fi)
            for h in getattr(s, "handlers", ()):
                self._taint_stmts(h.body, tainted, fi)

    def _flag_stmts(self, stmts, tainted: Set[str], fi: FuncInfo) -> None:
        mod = fi.module
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            if isinstance(s, (ast.If, ast.While)) and \
                    self._expr_taint(s.test, tainted, fi):
                self.report(mod, s.lineno, "host-sync",
                            f"Python branching on a traced value in "
                            f"{fi.qual} — retraces per value (or "
                            f"ConcretizationError) inside the "
                            f"jit/shard_map hot path")
            for n in self._walk_exprs(s):
                if not isinstance(n, ast.Call):
                    continue
                tgts = self.call_targets(n.func, fi, mod)
                ext = self._ext_of(tgts)
                argv = list(n.args) + [kw.value for kw in n.keywords]
                any_tainted = any(self._expr_taint(a, tainted, fi)
                                  for a in argv)
                if ext and ext.startswith("numpy") and any_tainted:
                    self.report(mod, n.lineno, "host-sync",
                                f"{ext} on a traced value in {fi.qual} — "
                                f"host sync inside the jit/shard_map hot "
                                f"path")
                elif ext and ext.startswith("builtins.") and \
                        ext.split(".")[-1] in CAST_BUILTINS and any_tainted:
                    self.report(mod, n.lineno, "host-sync",
                                f"{ext.split('.')[-1]}() materializes a "
                                f"traced value in {fi.qual} — host sync "
                                f"on the hot path")
                elif isinstance(n.func, ast.Attribute) and \
                        n.func.attr in SYNC_METHODS and \
                        self._expr_taint(n.func.value, tainted, fi):
                    self.report(mod, n.lineno, "host-sync",
                                f".{n.func.attr}() on a traced value in "
                                f"{fi.qual} — host sync on the hot path")
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(s, attr, None)
                if sub:
                    self._flag_stmts(sub, tainted, fi)
            for h in getattr(s, "handlers", ()):
                self._flag_stmts(h.body, tainted, fi)

    def _walk_exprs(self, stmt):
        """Expression nodes of one statement, not descending into nested
        statements or function definitions."""
        exprs = []
        for fname, value in ast.iter_fields(stmt):
            if fname in ("body", "orelse", "finalbody", "handlers"):
                continue
            vals = value if isinstance(value, list) else [value]
            for v in vals:
                if isinstance(v, ast.AST):
                    exprs.append(v)
        out = []
        while exprs:
            n = exprs.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            out.append(n)
            exprs.extend(c for c in ast.iter_child_nodes(n)
                         if isinstance(c, ast.AST))
        return out

    # -- host-sync: eager device->host->device round trips -----------------
    def run_round_trip(self) -> None:
        for mod in self.modules.values():
            if not mod.rel.startswith(SEMANTIC_SCOPE):
                continue
            for fi in mod.funcs:
                if fi in self.traced or isinstance(fi.node, ast.Lambda):
                    continue
                self._round_trip_fn(fi)

    def _rt_level(self, e, env, fi, silent=False) -> Tuple[int, frozenset]:
        """(level, host-pull origin lines): 0 none, 1 device, 2 host.
        ``silent`` evaluates without reporting (propagation passes)."""
        if isinstance(e, ast.Name):
            return env.get(e.id, (0, frozenset()))
        if isinstance(e, ast.Constant):
            return (0, frozenset())
        if isinstance(e, ast.Attribute):
            if e.attr in STATIC_ATTRS:
                return (0, frozenset())
            return self._rt_level(e.value, env, fi, silent)
        if isinstance(e, ast.Call):
            tgts = self.call_targets(e.func, fi, fi.module)
            ext = self._ext_of(tgts)
            argv = list(e.args) + [kw.value for kw in e.keywords]
            levels = [self._rt_level(a, env, fi, silent) for a in argv]
            lvl = max([l for l, _ in levels], default=0)
            orig = frozenset().union(*[o for _, o in levels]) \
                if levels else frozenset()
            if ext and ext.startswith("jax"):
                if lvl == 2 and not silent:
                    for line in sorted(orig):
                        self.report(
                            fi.module, line, "host-sync",
                            f"device->host->device round trip in "
                            f"{fi.qual}: device value pulled to host "
                            f"here feeds back into {ext} (line "
                            f"{e.lineno}) — keep it on device")
                return (1, frozenset())
            if ext and ext.startswith("numpy"):
                if lvl == 1:
                    return (2, frozenset({e.lineno}))
                return (lvl, orig)
            if ext and ext.startswith("builtins."):
                return (0, frozenset())
            if any(k == "func" and t in self.traced for k, t in tgts):
                return (1, frozenset())
            if isinstance(e.func, ast.Attribute) and \
                    e.func.attr in SYNC_METHODS:
                base = self._rt_level(e.func.value, env, fi, silent)
                if base[0] == 1:
                    return (2, frozenset({e.lineno}))
            if any(k == "func" for k, t in tgts):
                # a host-side library function: its arguments cross a
                # deliberate boundary; taint does not flow through
                return (0, frozenset())
            return (lvl, orig)
        if isinstance(e, ast.Lambda):
            return (0, frozenset())
        if isinstance(e, ast.AST):
            levels = [self._rt_level(c, env, fi, silent)
                      for c in ast.iter_child_nodes(e)
                      if isinstance(c, ast.AST)]
            if not levels:
                return (0, frozenset())
            return (max(l for l, _ in levels),
                    frozenset().union(*[o for _, o in levels]))
        return (0, frozenset())

    def _round_trip_fn(self, fi: FuncInfo) -> None:
        env: Dict[str, Tuple[int, frozenset]] = {}

        def do(stmts, evaluate):
            for s in stmts:
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    continue
                if isinstance(s, (ast.Assign, ast.AnnAssign,
                                  ast.AugAssign)):
                    value = getattr(s, "value", None)
                    if value is None:
                        continue
                    lvl = self._rt_level(value, env, fi) if evaluate \
                        else self._rt_assign_level(value, env, fi)
                    if lvl[0]:
                        targets = s.targets if isinstance(s, ast.Assign) \
                            else [s.target]
                        for t in targets:
                            for n in ast.walk(t):
                                if isinstance(n, ast.Name):
                                    old = env.get(n.id, (0, frozenset()))
                                    env[n.id] = (max(old[0], lvl[0]),
                                                 old[1] | lvl[1])
                    continue
                if evaluate:
                    for fname, v in ast.iter_fields(s):
                        if fname in ("body", "orelse", "finalbody",
                                     "handlers"):
                            continue
                        vals = v if isinstance(v, list) else [v]
                        for x in vals:
                            if isinstance(x, ast.AST):
                                self._rt_level(x, env, fi)
                if isinstance(s, ast.For):
                    lvl = self._rt_assign_level(s.iter, env, fi)
                    if lvl[0]:
                        for n in ast.walk(s.target):
                            if isinstance(n, ast.Name):
                                env[n.id] = lvl
                for attr in ("body", "orelse", "finalbody"):
                    sub = getattr(s, attr, None)
                    if sub:
                        do(sub, evaluate)
                for h in getattr(s, "handlers", ()):
                    do(h.body, evaluate)

        body = fi.node.body
        for _ in range(3):
            before = dict(env)
            do(body, evaluate=False)
            if env == before:
                break
        do(body, evaluate=True)

    def _rt_assign_level(self, e, env, fi):
        """Like _rt_level but silent (no findings) — propagation passes."""
        return self._rt_level(e, env, fi, silent=True)

    # -- axis-name ---------------------------------------------------------
    def run_axis_name(self) -> None:
        bound = self._bound_axis_names()
        for mod in self.modules.values():
            for fi in mod.funcs:
                calls = []
                for call in self._iter_calls(fi):
                    ext = self._ext_of(
                        self.call_targets(call.func, fi, mod))
                    leaf = ext.split(".")[-1] if ext else ""
                    if leaf in COLLECTIVES and (
                            ext.startswith("jax") or
                            ext.startswith("raft_tpu")):
                        calls.append((call, leaf))
                if not calls:
                    continue
                reachable = fi in self.wrapped
                emit = mod.rel.startswith(SEMANTIC_SCOPE)
                for call, leaf in calls:
                    axis = self._axis_arg(call, leaf)
                    if not reachable and emit:
                        self.report(
                            mod, call.lineno, "axis-name",
                            f"collective {leaf} in {fi.qual} is not "
                            f"reachable from any shard_map/pmap wrapper "
                            f"— its axis name is never bound")
                    elif emit and isinstance(axis, ast.Constant) and \
                            isinstance(axis.value, str) and bound and \
                            axis.value not in bound:
                        self.report(
                            mod, call.lineno, "axis-name",
                            f"collective {leaf} names axis "
                            f"{axis.value!r}, which no shard_map/pmap/"
                            f"mesh in the tree binds "
                            f"(bound: {sorted(bound)})")

    def _axis_arg(self, call: ast.Call, leaf: str):
        pos = COLLECTIVE_AXIS_POS.get(leaf, 1)
        if pos < len(call.args):
            return call.args[pos]
        for kw in call.keywords:
            if kw.arg == "axis_name":
                return kw.value
        return None

    def _bound_axis_names(self) -> Set[str]:
        bound: Set[str] = set()
        for mod in self.modules.values():
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                dotted = _dotted_expr(node.func) or ""
                leaf = dotted.split(".")[-1]
                if leaf == "Mesh":
                    cands = node.args[1:2] + [kw.value
                                              for kw in node.keywords
                                              if kw.arg == "axis_names"]
                    for c in cands:
                        bound |= set(_const_strs(c))
                elif leaf in ("P", "PartitionSpec"):
                    for a in node.args:
                        bound |= set(_const_strs(a))
                elif leaf == "pmap":
                    for kw in node.keywords:
                        if kw.arg == "axis_name":
                            bound |= set(_const_strs(kw.value))
        return bound

    # -- epoch-bump --------------------------------------------------------
    def run_epoch(self, mods=None) -> None:
        for mod in (mods if mods is not None else self.modules.values()):
            if not mod.rel.startswith(SEMANTIC_SCOPE):
                continue
            for fi in mod.funcs:
                if isinstance(fi.node, ast.Lambda) or \
                        fi.name in ("__init__", "__post_init__"):
                    continue
                self._epoch_fn(fi)

    def _is_storage_mut(self, s) -> Optional[int]:
        if isinstance(s, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = s.targets if isinstance(s, ast.Assign) else [s.target]
            for t in targets:
                elts = t.elts if isinstance(t, ast.Tuple) else [t]
                for e in elts:
                    if isinstance(e, ast.Attribute) and \
                            e.attr in STORAGE_ATTRS:
                        return s.lineno
        if isinstance(s, ast.Expr) and isinstance(s.value, ast.Call):
            dotted = _dotted_expr(s.value.func) or ""
            if dotted == "setattr" and len(s.value.args) >= 2:
                name = s.value.args[1]
                if not isinstance(name, ast.Constant) or \
                        name.value in STORAGE_ATTRS:
                    return s.lineno
        return None

    def _is_epoch_bump(self, s) -> bool:
        if isinstance(s, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = s.targets if isinstance(s, ast.Assign) else [s.target]
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Attribute) and "epoch" in n.attr:
                        return True
        return False

    def _epoch_fn(self, fi: FuncInfo) -> None:
        """Path-sensitive walk: each path carries (mutated_line|None,
        bumped) combos; a Return (or fall-off-the-end) on a path that
        mutated without bumping is a finding.  Paths that returned stop
        contributing (combo set empty)."""
        mod = fi.module

        def step(combos, s):
            line = self._is_storage_mut(s)
            if line is not None:
                combos = {(m if m is not None else line, b)
                          for m, b in combos}
            if self._is_epoch_bump(s):
                combos = {(m, True) for m, b in combos}
            return combos

        def walk(stmts, combos):
            for s in stmts:
                if not combos:
                    return combos
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    continue
                combos = step(combos, s)
                if isinstance(s, ast.Return):
                    for m, b in combos:
                        if m is not None and not b:
                            # anchored at the mutation (where a waiver
                            # naturally sits), naming the leaky return
                            self.report(
                                mod, m, "epoch-bump",
                                f"{fi.qual} mutates index storage here "
                                f"but returns (line {s.lineno}) without "
                                f"bumping .epoch — stale ResultCache "
                                f"entries stay servable")
                    return set()
                if isinstance(s, ast.If):
                    combos = (walk(s.body, set(combos)) |
                              walk(s.orelse, set(combos)))
                elif isinstance(s, (ast.For, ast.While)):
                    combos = combos | walk(s.body, set(combos))
                elif isinstance(s, ast.Try):
                    after = walk(s.body, set(combos))
                    for h in s.handlers:
                        after |= walk(h.body, set(combos))
                    after = walk(s.orelse, after) | set()
                    combos = walk(s.finalbody, after)
                elif isinstance(s, ast.With):
                    combos = walk(s.body, combos)
            return combos

        final = walk(fi.node.body, {(None, False)})
        for m, b in final:
            if m is not None and not b:
                self.report(mod, m, "epoch-bump",
                            f"{fi.qual} mutates index storage but can "
                            f"fall off the end without bumping .epoch")
                break

    # -- lock-discipline ---------------------------------------------------
    def run_lock(self, mods=None) -> None:
        for mod in (mods if mods is not None else self.modules.values()):
            if not mod.rel.startswith(SEMANTIC_SCOPE):
                continue
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.ClassDef):
                    self._lock_class(mod, node)

    def _lock_class(self, mod: ModuleInfo, cls: ast.ClassDef) -> None:
        lock_attrs: Set[str] = set()
        guarded: Set[str] = set()
        methods = [n for n in cls.body
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]
        for m in methods:
            for s in ast.walk(m):
                if isinstance(s, ast.Assign):
                    targets, value = s.targets, s.value
                elif isinstance(s, ast.AnnAssign) and s.value is not None:
                    targets, value = [s.target], s.value
                else:
                    continue
                for t in targets:
                    if not (isinstance(t, ast.Attribute) and
                            isinstance(t.value, ast.Name) and
                            t.value.id == "self"):
                        continue
                    dotted = (_dotted_expr(value.func) or "") \
                        if isinstance(value, ast.Call) else ""
                    leaf = dotted.split(".")[-1]
                    if leaf in ("Lock", "RLock"):
                        lock_attrs.add(t.attr)
                    elif m.name == "__init__" and (
                            isinstance(value,
                                       (ast.List, ast.Dict, ast.Set))
                            or leaf in CONTAINER_CTORS):
                        guarded.add(t.attr)
        if not lock_attrs or not guarded:
            return

        def locked_regions(m):
            """(node, under_lock) pairs via a recursive walk."""
            out = []

            def rec(n, locked):
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and n is not m:
                    return
                if isinstance(n, ast.With):
                    has = any(
                        isinstance(item.context_expr, ast.Attribute) and
                        item.context_expr.attr in lock_attrs
                        for item in n.items)
                    for c in n.body:
                        rec(c, locked or has)
                    return
                out.append((n, locked))
                for c in ast.iter_child_nodes(n):
                    rec(c, locked)

            for s in m.body:
                rec(s, False)
            return out

        # direct unlocked accesses per method, and locked call sites
        unlocked: Dict[str, List[int]] = {}
        call_sites: Dict[str, List[bool]] = {}
        for m in methods:
            if m.name == "__init__":
                continue
            for node, locked in locked_regions(m):
                if isinstance(node, ast.Attribute) and \
                        isinstance(node.value, ast.Name) and \
                        node.value.id == "self" and node.attr in guarded \
                        and not locked:
                    unlocked.setdefault(m.name, []).append(node.lineno)
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        isinstance(node.func.value, ast.Name) and \
                        node.func.value.id == "self":
                    call_sites.setdefault(node.func.attr, []).append(locked)
        for name, lines in sorted(unlocked.items()):
            sites = call_sites.get(name, [])
            if name.startswith("_") and sites and all(sites):
                continue   # private helper, only ever called under the lock
            for line in sorted(set(lines)):
                self.report(
                    mod, line, "lock-discipline",
                    f"{cls.name}.{name} touches guarded state "
                    f"({', '.join(sorted(guarded))} are shared with "
                    f"threads) outside `with self."
                    f"{sorted(lock_attrs)[0]}`")

    # -- sentinel ----------------------------------------------------------
    def _is_inf_literal(self, e) -> bool:
        if isinstance(e, ast.UnaryOp) and isinstance(e.op, ast.USub):
            return self._is_inf_literal(e.operand)
        dotted = _dotted_expr(e) or ""
        if dotted.split(".")[-1] == "inf" and dotted != "inf":
            return True
        if isinstance(e, ast.Call):
            d = _dotted_expr(e.func) or ""
            if d.split(".")[-1] == "float" and e.args and \
                    isinstance(e.args[0], ast.Constant) and \
                    str(e.args[0].value).lstrip("+-") == "inf":
                return True
        if isinstance(e, ast.Constant) and isinstance(e.value, float) and \
                (e.value == float("inf") or e.value == float("-inf")):
            return True
        return False

    def _has_neg_one(self, e) -> bool:
        for n in ast.walk(e):
            if isinstance(n, ast.UnaryOp) and isinstance(n.op, ast.USub) \
                    and isinstance(n.operand, ast.Constant) and \
                    n.operand.value == 1:
                return True
        return False

    def run_sentinel(self, mods=None) -> None:
        for mod in (mods if mods is not None else self.modules.values()):
            if mod.rel == SENTINEL_HOME or \
                    not any(mod.rel.startswith(p) or mod.rel == p
                            for p in SENTINEL_SCOPE):
                continue
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.Assign, ast.AnnAssign)):
                    v = getattr(node, "value", None)
                    arms = [v]
                    if isinstance(v, ast.IfExp):
                        arms = [v.body, v.orelse]
                    if v is not None and any(
                            a is not None and self._is_inf_literal(a)
                            for a in arms):
                        self.report(
                            mod, node.lineno, "sentinel",
                            "±inf sentinel literal — use raft_tpu.core."
                            "sentinels.worst_value / dummy_key_val")
                if not isinstance(node, ast.Call):
                    continue
                dotted = _dotted_expr(node.func) or ""
                if not (dotted.startswith("jnp.") or
                        dotted.startswith("jax.") or
                        dotted.startswith("np.") or
                        dotted.startswith("lax.")):
                    continue
                leaf = dotted.split(".")[-1]
                argv = list(node.args)
                for a in argv:
                    inner = a.body if isinstance(a, ast.IfExp) else a
                    arms = [a.body, a.orelse] if isinstance(a, ast.IfExp) \
                        else [inner]
                    if any(self._is_inf_literal(x) for x in arms):
                        self.report(
                            mod, node.lineno, "sentinel",
                            f"±inf sentinel literal in {leaf}() — use "
                            f"raft_tpu.core.sentinels.worst_value")
                if leaf in ("full", "full_like") and len(argv) >= 2 and \
                        self._has_neg_one(argv[1]):
                    self.report(
                        mod, node.lineno, "sentinel",
                        "-1 id sentinel literal in full() — use "
                        "raft_tpu.core.sentinels.PAD_ID")
                if leaf in ("where",) and len(argv) >= 3:
                    for a in argv[1:3]:
                        if (isinstance(a, ast.UnaryOp) and
                            self._has_neg_one(a)) or (
                                isinstance(a, ast.Call) and
                                (_dotted_expr(a.func) or "").endswith(
                                    "asarray") and a.args and
                                self._has_neg_one(a.args[0])):
                            self.report(
                                mod, node.lineno, "sentinel",
                                "-1 id sentinel literal in where() — use "
                                "raft_tpu.core.sentinels.PAD_ID")
                if leaf in ("asarray", "array") and argv and \
                        isinstance(argv[0], ast.UnaryOp) and \
                        self._has_neg_one(argv[0]):
                    self.report(
                        mod, node.lineno, "sentinel",
                        "-1 id sentinel literal — use raft_tpu.core."
                        "sentinels.PAD_ID / pad_id")
                for kw in node.keywords:
                    if kw.arg == "constant_values" and \
                            self._has_neg_one(kw.value):
                        self.report(
                            mod, node.lineno, "sentinel",
                            "-1 pad sentinel in constant_values — use "
                            "raft_tpu.core.sentinels.PAD_ID")

    # -- wall-clock --------------------------------------------------------
    def run_wall_clock(self, mods=None) -> None:
        """serve/ and lifecycle/ must read the injected clock: a direct
        ``time.time()`` / ``time.monotonic()`` / ``time.perf_counter()``
        / ``time.sleep()`` CALL in a scheduling, health, or hedging
        decision is unreplayable and unfakeable in tests.  Referencing
        ``time.monotonic`` without calling it (the constructor default
        that IS the injection point) is legal — only Call nodes flag."""
        for mod in (mods if mods is not None else self.modules.values()):
            if not any(mod.rel.startswith(p) for p in WALL_CLOCK_SCOPE):
                continue
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                dotted = _dotted_expr(node.func)
                if not dotted:
                    continue
                head, _, rest = dotted.partition(".")
                resolved = mod.imports.get(head, head)
                if rest:
                    resolved = f"{resolved}.{rest}"
                if resolved in WALL_CLOCK_CALLS:
                    self.report(
                        mod, node.lineno, "wall-clock",
                        f"direct {resolved}() call — serve/ and "
                        f"lifecycle/ read the injected clock (pass "
                        f"clock=/monotonic=/sleep= through instead)")

    # -- recompile-risk ----------------------------------------------------
    def run_recompile_risk(self) -> None:
        """Eager (untraced) code that materializes a device value to a
        host int and feeds it into an array EXTENT: every distinct
        value bakes a fresh shape, so every downstream jit consumer
        recompiles per value.  Traced functions are excluded — there
        the int() itself is host-sync's finding."""
        for mod in self.modules.values():
            if not mod.rel.startswith(SEMANTIC_SCOPE):
                continue
            for fi in mod.funcs:
                if fi in self.traced or isinstance(fi.node, ast.Lambda):
                    continue
                self._recompile_fn(fi)

    def _is_device_expr(self, e, fi: FuncInfo) -> bool:
        """Any jax.* call in the subtree — the value lives on device."""
        for n in ast.walk(e):
            if isinstance(n, ast.Call):
                ext = self._ext_of(
                    self.call_targets(n.func, fi, fi.module))
                if ext and ext.startswith("jax"):
                    return True
        return False

    def _dyn_extent(self, e, dyn: Dict[str, int],
                    fi: FuncInfo) -> Optional[int]:
        """Origin line if ``e`` carries a data-dependent host scalar
        (a device value pulled through int()/float()), else None.
        ``.shape``-family attributes are static; pow2 bucketing
        (next_pow2 / .bit_length) bounds the class count and
        sanitizes; jax calls yield device values (not host extents);
        resolved library functions are a deliberate boundary."""
        if isinstance(e, ast.Name):
            return dyn.get(e.id)
        if isinstance(e, ast.Constant):
            return None
        if isinstance(e, ast.Attribute):
            if e.attr in STATIC_ATTRS:
                return None
            return self._dyn_extent(e.value, dyn, fi)
        if isinstance(e, ast.Call):
            tgts = self.call_targets(e.func, fi, fi.module)
            ext = self._ext_of(tgts)
            leaf = ext.split(".")[-1] if ext else ""
            if ext in ("builtins.int", "builtins.float"):
                if any(self._is_device_expr(a, fi) for a in e.args):
                    return e.lineno            # the materialization
                for a in e.args:
                    got = self._dyn_extent(a, dyn, fi)
                    if got is not None:
                        return got
                return None
            if leaf in BUCKET_FNS:
                return None
            if isinstance(e.func, ast.Attribute) and \
                    e.func.attr in BUCKET_METHODS:
                return None
            if ext and ext.startswith("jax"):
                return None
            if any(k == "func" for k, _ in tgts):
                return None
            args = list(e.args) + [kw.value for kw in e.keywords]
            for a in args:
                got = self._dyn_extent(a, dyn, fi)
                if got is not None:
                    return got
            return None
        if isinstance(e, ast.Lambda):
            return None
        if isinstance(e, ast.AST):
            for c in ast.iter_child_nodes(e):
                if isinstance(c, ast.AST):
                    got = self._dyn_extent(c, dyn, fi)
                    if got is not None:
                        return got
        return None

    def _recompile_fn(self, fi: FuncInfo) -> None:
        mod = fi.module
        # statement list of this function, nested defs excluded (they
        # are their own FuncInfos)
        stmts = []
        stack = [] if isinstance(fi.node, ast.Lambda) else \
            list(fi.node.body)
        while stack:
            s = stack.pop()
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef, ast.Lambda)):
                continue
            stmts.append(s)
            for attr in ("body", "orelse", "finalbody"):
                stack.extend(getattr(s, attr, None) or ())
            for h in getattr(s, "handlers", ()):
                stack.extend(h.body)
            for c in getattr(s, "cases", ()):   # ast.Match arms
                stack.extend(c.body)
        stmts.sort(key=lambda s: s.lineno)

        top = set() if isinstance(fi.node, ast.Lambda) \
            else set(fi.node.body)
        dyn: Dict[str, int] = {}
        for _ in range(3):   # small fixpoint for chained assignments
            changed = False
            for s in stmts:
                if not isinstance(s, (ast.Assign, ast.AnnAssign,
                                      ast.AugAssign)):
                    continue
                v = getattr(s, "value", None)
                if v is None:
                    continue
                origin = self._dyn_extent(v, dyn, fi)
                if origin is None:
                    # A plain rebind to a clean value SANITIZES the
                    # name (`cap = next_pow2(cap)` — the remedy the
                    # finding message itself recommends).  Only at the
                    # function's top level, where line order IS
                    # execution order — a clean rebind inside one
                    # branch must not mask taint from a sibling arm.
                    # AugAssign keeps taint: `cap += 1` derives from
                    # the old value.
                    if s in top and isinstance(s, (ast.Assign,
                                                   ast.AnnAssign)):
                        targets = s.targets if isinstance(s, ast.Assign) \
                            else [s.target]
                        for t in targets:
                            for n in ast.walk(t):
                                if isinstance(n, ast.Name):
                                    dyn.pop(n.id, None)
                    continue
                targets = s.targets if isinstance(s, ast.Assign) \
                    else [s.target]
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name) and n.id not in dyn:
                            dyn[n.id] = origin
                            changed = True
            if not changed:
                break

        for call in self._iter_calls(fi):
            ext = self._ext_of(self.call_targets(call.func, fi, mod))
            if not ext or not ext.startswith("jax"):
                continue
            leaf = ext.split(".")[-1]
            extent_args = []
            if leaf in SHAPE_CTORS:
                extent_args += call.args[:1]
            elif leaf == "arange" and len(call.args) == 1:
                # multi-arg arange: start/stop offsets shift VALUES,
                # the extent (stop - start) usually stays static
                extent_args += call.args[:1]
            extent_args += [kw.value for kw in call.keywords
                            if kw.arg in ("shape", "size")]
            for a in extent_args:
                origin = self._dyn_extent(a, dyn, fi)
                if origin is not None:
                    self.report(
                        mod, call.lineno, "recompile-risk",
                        f"{leaf}() in {fi.qual} sized by a host int of "
                        f"a device value (materialized at line "
                        f"{origin}) — each distinct extent bakes a new "
                        f"shape and recompiles every downstream jit; "
                        f"use a static or pow2-bucketed capacity "
                        f"(next_pow2), or waive a build-time one-shot")
                    break

    # -- style / cite ------------------------------------------------------
    def run_style(self, mods=None) -> None:
        for mod in (mods if mods is not None else self.modules.values()):
            text = "\n".join(mod.lines)
            if text and not text.endswith("\n") and mod.lines[-1] != "":
                self.report(mod, len(mod.lines), "style",
                            "missing newline at EOF")
            for ln, line in enumerate(mod.lines, 1):
                if line.startswith("\t"):
                    self.report(mod, ln, "style", "tab indentation")
                if line != line.rstrip():
                    self.report(mod, ln, "style", "trailing whitespace")
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.ImportFrom) and any(
                        a.name == "*" for a in node.names):
                    self.report(mod, node.lineno, "style",
                                "wildcard import")

    def run_cite(self, mods=None) -> None:
        for mod in (mods if mods is not None else self.modules.values()):
            if not mod.rel.startswith("raft_tpu/") or \
                    mod.rel.endswith("__init__.py"):
                continue
            doc = ast.get_docstring(mod.tree) or ""
            if "ref:" not in doc.lower() and \
                    "ref pattern" not in doc.lower():
                self.report(mod, 1, "cite",
                            "module docstring lacks a reference citation "
                            "('Ref:'), the parity-evidence convention")

    # -- driver ------------------------------------------------------------
    def run(self, checks: Sequence[str],
            restrict: Optional[Set[str]] = None) -> List[Finding]:
        """Run ``checks``; with ``restrict`` (a set of rel paths) the
        LOCAL checks only visit those modules — graph checks always see
        the whole tree (an interprocedural finding may live far from
        the module that causes it).  Idempotent: each call starts from
        empty findings, so the cache driver can run the local and graph
        tiers as two separate calls."""
        self.findings = []
        self.waived = []
        self._seen = set()
        self._seen_waived = set()
        self.findings.extend(
            f for f in self.parse_errors
            if restrict is None or f.rel in restrict)
        mods = [m for m in self.modules.values()
                if restrict is None or m.rel in restrict]
        need_graph = set(GRAPH_CHECKS) & set(checks)
        if need_graph:
            self.build_graph()
        if "style" in checks:
            self.run_style(mods)
        if "cite" in checks:
            self.run_cite(mods)
        if "host-sync" in checks:
            self.run_host_sync()
            self.run_round_trip()
        if "axis-name" in checks:
            self.run_axis_name()
        if "epoch-bump" in checks:
            self.run_epoch(mods)
        if "lock-discipline" in checks:
            self.run_lock(mods)
        if "sentinel" in checks:
            self.run_sentinel(mods)
        if "wall-clock" in checks:
            self.run_wall_clock(mods)
        if "recompile-risk" in checks:
            self.run_recompile_risk()
        self.waived.sort(key=lambda f: (f.rel, f.line, f.check, f.msg))
        return sorted(self.findings,
                      key=lambda f: (f.rel, f.line, f.check, f.msg))


def analyze_sources(files: Dict[str, str],
                    checks: Optional[Sequence[str]] = None
                    ) -> List[Finding]:
    """Run the analyzer over an in-memory {relpath: source} tree (the
    test harness entry point)."""
    return Analyzer(files).run(tuple(checks) if checks else CHECKS)


def repo_files(root: Path = ROOT) -> Dict[str, str]:
    files: Dict[str, str] = {}
    for top in SCAN:
        base = root / top
        if not base.exists():
            continue
        for path in sorted(base.rglob("*.py")):
            if "__pycache__" in path.parts:
                continue
            rel = path.relative_to(root).as_posix()
            files[rel] = path.read_text(encoding="utf-8")
    return files


def analyze_repo(root: Path = ROOT,
                 checks: Optional[Sequence[str]] = None) -> List[Finding]:
    return analyze_sources(repo_files(root), checks)


def cache_module():
    """Load ci/analyze_cache.py by path (ci/ is not a package; this
    module itself is loaded standalone by tests and by `python
    ci/analyze.py`, so a plain import has no anchor)."""
    import importlib.util

    name = "graft_analyze_cache"
    if name in sys.modules:
        return sys.modules[name]
    path = Path(__file__).resolve().parent / "analyze_cache.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def analyze_repo_cached(root: Path = ROOT,
                        checks: Optional[Sequence[str]] = None,
                        cache_dir: Optional[Path] = None,
                        use_cache: bool = True):
    """Cached analyze over a repo tree.

    Returns ``(findings, waived, stats)`` — ``stats`` is an
    ``analyze_cache.CacheStats`` (None when ``use_cache=False``).  The
    cache is PURE memoization: findings are identical to an uncached
    run (check selection is applied when assembling results; cache
    entries always hold the full per-tier check set, so a partial
    ``--check`` run can never poison a later full run).
    """
    cs = tuple(checks) if checks else CHECKS
    files = repo_files(root)
    if not use_cache:
        an = Analyzer(files)
        findings = an.run(cs)
        return findings, list(an.waived), None
    import types

    ac = cache_module()
    cdir = Path(cache_dir) if cache_dir is not None \
        else Path(root) / ".analyze_cache"
    api = types.SimpleNamespace(Analyzer=Analyzer, Finding=Finding,
                                LOCAL_CHECKS=LOCAL_CHECKS,
                                GRAPH_CHECKS=GRAPH_CHECKS)
    return ac.run_cached(api, files, cs, cdir)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="graft-analyze: TPU tracing-safety & concurrency "
                    "static analyzer")
    ap.add_argument("--check", action="append", choices=CHECKS,
                    help="run only this check (repeatable; default all)")
    ap.add_argument("--list-checks", action="store_true")
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--no-cache", action="store_true",
                    help="bypass the incremental result cache")
    ap.add_argument("--cache-dir", default=None,
                    help="cache directory (default <root>/.analyze_cache)")
    ap.add_argument("--stats", action="store_true",
                    help="print a cache/waiver summary line")
    ap.add_argument("--show-waived", action="store_true",
                    help="print waived findings (never affect exit code)")
    args = ap.parse_args(argv)
    if args.list_checks:
        for c in CHECKS:
            print(c)
        return 0
    checks = tuple(args.check) if args.check else CHECKS
    findings, waived, stats = analyze_repo_cached(
        Path(args.root), checks,
        cache_dir=Path(args.cache_dir) if args.cache_dir else None,
        use_cache=not args.no_cache)
    for f in findings:
        print(f.render())
    if args.show_waived:
        for f in waived:
            print(f"{f.rel}:{f.line}: [{f.check}] waived"
                  + (f" — {f.msg}" if f.msg else ""))
    print(f"graft-analyze: {len(findings)} finding(s) "
          f"[checks: {', '.join(checks)}]")
    if args.stats:
        if stats is None:
            print(f"graft-analyze-cache: disabled; "
                  f"{len(waived)} waived")
        else:
            graph = "skipped" if stats.graph_hit is None \
                else ("hit" if stats.graph_hit else "miss")
            print(f"graft-analyze-cache: modules {stats.mod_hits} hit / "
                  f"{stats.mod_misses} miss, graph {graph}, "
                  f"{stats.pruned} pruned; {len(waived)} waived")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
