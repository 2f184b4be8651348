"""JAX hazard rules for graftcheck.

Rules emitted by :func:`check_module`:

- ``jax-retrace-hazard`` — Python control flow (``if``/``while``/
  ``range()`` loop bound) on a *traced* parameter inside a function
  handed to ``jax.jit``. Every distinct concrete value retraces and
  recompiles the program; the serving perf story rests on occupancy
  changes NOT retracing. Static things are exempt: parameters named in
  ``static_argnums``/``static_argnames``, ``x is None`` checks (resolved
  at trace time), ``.shape``/``.ndim``/``.dtype``/``.size`` access, and
  ``isinstance``/``len``/``hasattr``/``callable`` calls — those are all
  trace-time constants.
- ``jax-varying-capture`` — a jitted function closes over a name its
  enclosing function reassigns in a loop or augments; each new value is
  baked in at trace time, so the jit either silently uses a stale value
  or retraces per call.
- ``jax-host-sync-in-hot-loop`` — ``.item()``, ``float()``, ``bool()``,
  ``int()``, ``np.asarray``/``np.array`` on a non-literal inside the
  decode/coalescer/fit hot loops. Each is a device→host sync that
  serializes the dispatch pipeline.
- ``jax-donation-misuse`` — an argument passed through a
  ``donate_argnums`` position is read again after the dispatch; the
  donated buffer is invalid once XLA reuses it.
- ``jax-untraced-randomness`` — ``np.random.*`` / ``random.*`` called
  inside a jitted body. The call runs once at trace time and bakes a
  constant into the program; ``jax.random`` with ``fold_in`` is the
  sanctioned path.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from deeplearning4j_tpu.analysis.core import Finding

# attribute access on a traced value that is still static at trace time
SAFE_ATTRS = {"shape", "ndim", "dtype", "size"}
# calls whose result on a traced value is a trace-time constant
SAFE_CALLS = {"isinstance", "len", "hasattr", "callable", "type", "getattr"}

# functions that ARE the serving/training hot loops; one host sync here
# stalls every slot/request in the batch
HOT_FUNCTIONS = {
    "_decode_once", "_prefill_wave",              # generation slot loop
    "_spec_decode_once",                          # speculative verify loop
    "_coalesce_loop", "_complete_loop",           # inference coalescer
    "_dispatch_batch", "_dispatch_fwd",           # inference dispatch
    "_run_block", "fit_stream",                   # fused-fit driver loop
    "_route_once", "_replica_done",               # fleet router hot path
    "_monitor_loop",                              # fleet redispatch/hedge
    "_service_parked",                            # fleet resume path
    "_snapshot_slot", "_adopt_into_slot",         # KV handoff export/adopt
    "_tier_route",                                # disagg tier routing
    "_transfer_loop",                             # prefill->decode export
    "_autoscale_tick",                            # autoscaler control loop
    "_soak_arrival_loop",                         # load-generator pacing
    "_snapshot_families",                         # /metrics scrape path
    "_proj",                                      # fused-dequant projection
    "_quantize_kv",                               # int8 KV write quantizer
    "_knn_coalesce_once",                         # knn query coalescer
    "_knn_dispatch_batch", "_dispatch_knn",       # knn search dispatch
    "_knn_complete_loop",                         # knn completer fetch
    "_paged_forward",                             # paged-KV decode read+write
    "paged_attend",                               # helper-seam dispatch
    "resolve_paged_backend",                      # helper-seam selection
    "_shard_pool",                                # mesh pool placement
    "_reshard_snapshot",                          # adopt-side payload reshard
    "_sharded_write_attend",                      # shard_map write+attend body
    "_gossip_loop",                               # federation router tick
    "_route_host",                                # federation dispatch path
    "_harvest_host",                              # federation crash harvest
    "_rag_retrieve_done",                         # rag knn-tier completion
    "_rag_assemble_dispatch",                     # rag tier-boundary route
    "_rag_generate_done",                         # rag generate completion
    "_probe_local_rank",                          # per-device IVF probe body
}

SYNC_BUILTINS = {"float", "bool", "int"}


def _dotted(node: ast.AST) -> Optional[str]:
    """'np.random.normal' for Attribute chains, 'float' for Names."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _const_str_set(node: ast.AST) -> Set[str]:
    """Names out of a constant str / tuple-or-list of constant strs."""
    out: Set[str] = set()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        out.add(node.value)
    elif isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.add(e.value)
    return out


def _const_int_set(node: ast.AST) -> Set[int]:
    out: Set[int] = set()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        out.add(node.value)
    elif isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.add(e.value)
    return out


def _param_names(fn: ast.AST) -> List[str]:
    a = fn.args
    names = [p.arg for p in getattr(a, "posonlyargs", [])]
    names += [p.arg for p in a.args]
    return names


def _jit_call_info(call: ast.Call, jit_names: Set[str]):
    """If ``call`` is jax.jit(target, ...) return (target_node,
    static_names, static_nums, donate_nums); else None."""
    name = _dotted(call.func)
    if name not in jit_names:
        return None
    target = call.args[0] if call.args else None
    static_names: Set[str] = set()
    static_nums: Set[int] = set()
    donate_nums: Set[int] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            static_names |= _const_str_set(kw.value)
        elif kw.arg == "static_argnums":
            static_nums |= _const_int_set(kw.value)
        elif kw.arg in ("donate_argnums", "donate_argnames"):
            donate_nums |= _const_int_set(kw.value)
    return target, static_names, static_nums, donate_nums


def _decorator_jit_info(dec: ast.AST, jit_names: Set[str]):
    """(static_names, static_nums) if ``dec`` is a jit decorator —
    bare ``@jax.jit``, ``@jax.jit(...)`` or ``@partial(jax.jit, ...)``."""
    if _dotted(dec) in jit_names:
        return set(), set()
    if isinstance(dec, ast.Call):
        fname = _dotted(dec.func)
        if fname in jit_names:
            info = _jit_call_info(dec, jit_names)
            return info[1], info[2]
        if fname in ("partial", "functools.partial") and dec.args \
                and _dotted(dec.args[0]) in jit_names:
            statics: Set[str] = set()
            nums: Set[int] = set()
            for kw in dec.keywords:
                if kw.arg == "static_argnames":
                    statics |= _const_str_set(kw.value)
                elif kw.arg == "static_argnums":
                    nums |= _const_int_set(kw.value)
            return statics, nums
    return None


class _ModuleScan(ast.NodeVisitor):
    """Collects jit aliases and walks scopes, resolving which local
    function defs end up wrapped in jax.jit."""

    def __init__(self, relpath: str):
        self.relpath = relpath
        self.findings: List[Finding] = []
        self.jit_names = {"jax.jit", "jit"}
        # scope bookkeeping: stack of (kind, name, node)
        self.scope: List[Tuple[str, str, ast.AST]] = []

    # ---- scope helpers -------------------------------------------------
    def _scope_name(self) -> str:
        names = [n for kind, n, _ in self.scope if kind in ("class", "func")]
        return ".".join(names) if names else "<module>"

    # ---- module entry --------------------------------------------------
    def run(self, tree: ast.Module) -> List[Finding]:
        for stmt in tree.body:
            if isinstance(stmt, ast.ImportFrom) and stmt.module == "jax":
                for alias in stmt.names:
                    if alias.name == "jit":
                        self.jit_names.add(alias.asname or "jit")
        self._walk_body(tree.body, local_defs={})
        return self.findings

    # ---- generic body walk: find defs, classify jit targets ------------
    @staticmethod
    def _scope_nodes(body):
        """Every node in this scope, NOT descending into nested
        def/class bodies (the nested def node itself is yielded). A def
        inside a `for`/`if` block still belongs to this scope."""
        stack = list(body)
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                stack.extend(ast.iter_child_nodes(node))

    def _walk_body(self, body, local_defs: Dict[str, ast.AST]):
        """Scan one scope: (1) register its function defs (any nesting
        depth short of a nested scope), (2) resolve which of them get
        wrapped in jax.jit, (3) run the jitted checks and recurse."""
        nodes = list(self._scope_nodes(body))
        for node in nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                local_defs[node.name] = node

        jitted: Dict[str, Tuple[Set[str], Set[int]]] = {}
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            info = _jit_call_info(node, self.jit_names)
            if info is None:
                continue
            target, statics, nums, _don = info
            if isinstance(target, ast.Name) and target.id in local_defs:
                prev = jitted.get(target.id, (set(), set()))
                jitted[target.id] = (prev[0] | statics, prev[1] | nums)

        for node in nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                statics: Optional[Tuple[Set[str], Set[int]]] = None
                for dec in node.decorator_list:
                    got = _decorator_jit_info(dec, self.jit_names)
                    if got is not None:
                        statics = got
                        break
                if statics is None and node.name in jitted:
                    statics = jitted[node.name]
                if statics is not None:
                    self._check_jitted(node, statics[0], statics[1])
                self._enter_function(node, local_defs)
            elif isinstance(node, ast.ClassDef):
                self.scope.append(("class", node.name, node))
                self._walk_body(node.body, local_defs={})
                self.scope.pop()

    def _enter_function(self, fn, outer_defs: Dict[str, ast.AST]):
        self.scope.append(("func", fn.name, fn))
        if fn.name in HOT_FUNCTIONS:
            self._check_hot_loop(fn)
        self._check_donation(fn)
        # recurse into direct statement list (nested defs/classes)
        self._walk_body(fn.body, local_defs=dict(outer_defs))
        self.scope.pop()

    # ---- rule: retrace hazards inside a jitted def ---------------------
    def _check_jitted(self, fn, static_names: Set[str],
                      static_nums: Set[int]):
        params = _param_names(fn)
        traced = set(params) - static_names
        for i in static_nums:
            if 0 <= i < len(params):
                traced.discard(params[i])
        traced.discard("self")
        traced.discard("cls")

        scope = self._scope_name() + "." + fn.name \
            if self.scope else fn.name

        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                continue  # don't descend rule state into nested defs
            if isinstance(node, (ast.If, ast.While)):
                kind = "if" if isinstance(node, ast.If) else "while"
                for name in sorted(self._traced_in_test(node.test, traced)):
                    self.findings.append(Finding(
                        rule="jax-retrace-hazard", path=self.relpath,
                        line=node.lineno, col=node.col_offset, scope=scope,
                        detail=f"{fn.name}:{kind}:{name}",
                        message=(f"Python `{kind}` on traced parameter "
                                 f"`{name}` inside jitted `{fn.name}` — "
                                 "every distinct value retraces; use "
                                 "jnp.where/lax.cond or mark it static"),
                    ))
            elif isinstance(node, ast.For):
                it = node.iter
                if isinstance(it, ast.Call) and _dotted(it.func) == "range":
                    hazards = set()
                    for a in it.args:
                        hazards |= self._traced_in_test(a, traced)
                    for name in sorted(hazards):
                        self.findings.append(Finding(
                            rule="jax-retrace-hazard", path=self.relpath,
                            line=node.lineno, col=node.col_offset,
                            scope=scope, detail=f"{fn.name}:range:{name}",
                            message=(f"`range()` over traced parameter "
                                     f"`{name}` inside jitted `{fn.name}` "
                                     "— the loop unrolls per traced value;"
                                     " use lax.scan/fori_loop"),
                        ))
            elif isinstance(node, ast.Call):
                dn = _dotted(node.func)
                if dn and (dn.startswith("np.random.")
                           or dn.startswith("numpy.random.")
                           or dn.startswith("random.")):
                    self.findings.append(Finding(
                        rule="jax-untraced-randomness", path=self.relpath,
                        line=node.lineno, col=node.col_offset, scope=scope,
                        detail=f"{fn.name}:{dn}",
                        message=(f"`{dn}` inside jitted `{fn.name}` runs "
                                 "once at trace time and bakes a constant "
                                 "in — use jax.random with fold_in"),
                    ))

        self._check_varying_capture(fn, scope)

    def _traced_in_test(self, expr: ast.AST, traced: Set[str]) -> Set[str]:
        """Traced parameter names whose *value* the test depends on.
        `x is None`, `.shape`-family access, and isinstance/len/... calls
        are static at trace time and don't count."""
        out: Set[str] = set()

        def rec(e):
            if isinstance(e, ast.Name):
                if e.id in traced:
                    out.add(e.id)
            elif isinstance(e, ast.BoolOp):
                for v in e.values:
                    rec(v)
            elif isinstance(e, ast.UnaryOp):
                rec(e.operand)
            elif isinstance(e, ast.Compare):
                if all(isinstance(op, (ast.Is, ast.IsNot)) for op in e.ops):
                    return  # identity vs None: concrete at trace time
                rec(e.left)
                for c in e.comparators:
                    rec(c)
            elif isinstance(e, ast.BinOp):
                rec(e.left)
                rec(e.right)
            elif isinstance(e, ast.Attribute):
                if e.attr in SAFE_ATTRS:
                    return  # x.shape[...] etc. are static
                rec(e.value)
            elif isinstance(e, ast.Subscript):
                rec(e.value)
                rec(e.slice)
            elif isinstance(e, ast.Call):
                if isinstance(e.func, ast.Name) and e.func.id in SAFE_CALLS:
                    return
                for a in e.args:
                    rec(a)
                for k in e.keywords:
                    rec(k.value)
            elif isinstance(e, (ast.Tuple, ast.List, ast.Set)):
                for x in e.elts:
                    rec(x)
            elif isinstance(e, ast.IfExp):
                rec(e.test)
                rec(e.body)
                rec(e.orelse)

        rec(expr)
        return out

    # ---- rule: per-call-varying closure capture ------------------------
    def _check_varying_capture(self, fn, scope: str):
        encl = None
        for kind, _n, node in reversed(self.scope):
            if kind == "func":
                encl = node
                break
        if encl is None:
            return

        local: Set[str] = set(_param_names(fn))
        if fn.args.vararg:
            local.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            local.add(fn.args.kwarg.arg)
        local |= {p.arg for p in fn.args.kwonlyargs}
        loads: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, (ast.Store,)):
                    local.add(node.id)
                elif isinstance(node.ctx, ast.Load):
                    loads.add(node.id)
        free = loads - local

        # in the enclosing function (outside fn itself): does any free
        # name get augmented, or re-assigned inside a loop?
        varying: Dict[str, int] = {}

        def scan(node, in_loop: bool):
            if node is fn:
                return
            if isinstance(node, (ast.For, ast.While)):
                if isinstance(node, ast.For):
                    # the loop target itself varies per iteration
                    for t in ast.walk(node.target):
                        if isinstance(t, ast.Name) and t.id in free:
                            varying.setdefault(t.id, node.lineno)
                for child in ast.iter_child_nodes(node):
                    scan(child, True)
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not encl:
                return
            if isinstance(node, ast.AugAssign) \
                    and isinstance(node.target, ast.Name) \
                    and node.target.id in free:
                varying.setdefault(node.target.id, node.lineno)
            elif isinstance(node, ast.Assign) and in_loop:
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id in free:
                        varying.setdefault(t.id, node.lineno)
            for child in ast.iter_child_nodes(node):
                scan(child, in_loop)

        scan(encl, False)
        for name in sorted(varying):
            self.findings.append(Finding(
                rule="jax-varying-capture", path=self.relpath,
                line=varying[name], col=0, scope=scope,
                detail=f"{fn.name}:{name}",
                message=(f"jitted `{fn.name}` closes over `{name}`, which "
                         f"`{encl.name}` rebinds per iteration — the jit "
                         "baked the trace-time value in; pass it as an "
                         "argument instead"),
            ))

    # ---- rule: host sync inside hot loops ------------------------------
    def _check_hot_loop(self, fn):
        scope = self._scope_name()  # fn already pushed on the stack
        seq: Dict[str, int] = {}   # occurrence index per call shape —
        # keeps the finding key stable while surrounding lines move
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            dn = _dotted(node.func)
            hit = None
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                hit = ".item()"
            elif dn in SYNC_BUILTINS and len(node.args) == 1 \
                    and not isinstance(node.args[0], ast.Constant):
                hit = f"{dn}()"
            elif dn in ("np.asarray", "np.array",
                        "numpy.asarray", "numpy.array") and node.args \
                    and not isinstance(node.args[0], ast.Constant):
                hit = dn
            if hit:
                seq[hit] = seq.get(hit, 0) + 1
                self.findings.append(Finding(
                    rule="jax-host-sync-in-hot-loop", path=self.relpath,
                    line=node.lineno, col=node.col_offset, scope=scope,
                    detail=f"{fn.name}:{hit}:{seq[hit]}",
                    message=(f"`{hit}` in hot loop `{fn.name}` forces a "
                             "device→host sync per iteration — batch the "
                             "fetch or keep the value on device"),
                ))

    # ---- rule: donated buffer read after dispatch ----------------------
    def _check_donation(self, fn):
        scope = self._scope_name()  # fn already pushed on the stack
        jit_fns: Dict[str, Set[int]] = {}
        # donated[text] = (line of donating call)
        donated: Dict[str, int] = {}

        events = []  # (line, col, kind, payload)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call):
                info = _jit_call_info(node.value, self.jit_names)
                if info and info[3]:
                    events.append((node.lineno, node.col_offset, "jitdef",
                                   (node.targets[0].id, info[3])))
                    continue
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                events.append((node.lineno, node.col_offset, "call", node))
            if isinstance(node, (ast.Name, ast.Attribute)):
                text = _dotted(node)
                if text is None:
                    continue
                ctx = getattr(node, "ctx", None)
                kind = "store" if isinstance(ctx, ast.Store) else \
                    "load" if isinstance(ctx, ast.Load) else None
                if kind:
                    events.append((node.lineno, node.col_offset, kind,
                                   (text, node)))

        # order: within one line, loads/calls happen BEFORE the store of
        # an assignment target (`buf = step(buf, x)` rebinds AFTER the
        # donating call, so the donation is cleared, not reported)
        rank = {"jitdef": 0, "load": 1, "call": 2, "store": 3}
        events.sort(key=lambda e: (e[0], rank[e[2]], e[1]))
        # loads that are arguments of the donating call itself
        skip_loads: Set[int] = set()
        for line, col, kind, payload in events:
            if kind == "jitdef":
                name, dons = payload
                jit_fns[name] = dons
            elif kind == "call":
                call = payload
                fname = call.func.id
                if fname in jit_fns:
                    for pos in jit_fns[fname]:
                        if pos < len(call.args):
                            text = _dotted(call.args[pos])
                            if text:
                                donated[text] = line
                                for sub in ast.walk(call.args[pos]):
                                    skip_loads.add(id(sub))
            elif kind == "store":
                text, _node = payload
                donated.pop(text, None)
            elif kind == "load":
                text, node = payload
                if id(node) in skip_loads:
                    continue
                if text in donated and line > donated[text]:
                    self.findings.append(Finding(
                        rule="jax-donation-misuse", path=self.relpath,
                        line=line, col=col, scope=scope,
                        detail=f"{fn.name}:{text}",
                        message=(f"`{text}` was donated to a jitted call "
                                 f"(line {donated[text]}) and read again —"
                                 " the buffer may already be reused; "
                                 "rebind the output instead"),
                    ))
                    donated.pop(text, None)  # one finding per donation


def check_module(tree: ast.Module, relpath: str) -> List[Finding]:
    return _ModuleScan(relpath).run(tree)
