"""Device-discipline rules for the jit/Pallas layer (ISSUE 10 pillar 3).

The repo's most failure-prone surface — the device layer — had zero
static coverage: PR 9 detects recompile storms at RUNTIME, and PR 8's
kernels rely on hand-checked VMEM layout arithmetic.  Four rules hold
the "compile the whole program" discipline statically:

- ``host-sync``: a host synchronization — ``np.asarray``/``np.array``,
  ``float(...)``, ``.item()``, ``jax.device_get``,
  ``.block_until_ready()`` — applied to the RESULT of a
  devicewatch-jit program inside the serving path (``query/``,
  ``memstore/devicestore.py``, ``parallel/``, ``ops/``) without a
  ``# host-sync-ok: <reason>`` annotation.  Every such readback stalls
  the device pipeline for a host round trip; the serving path earns
  exactly the readbacks it declares.  Detection is dataflow-based
  (taint from jit-program call results), so the hundreds of
  ``np.asarray`` calls on host data never fire.
- ``host-sync-annotation``: a ``# host-sync-ok:`` comment with no
  reason, or one sitting on a line with no detected host sync — stale
  annotations must not rot silently (the stale-suppression principle).
- ``recompile-hazard``: a devicewatch-jit call site passing a
  shape-deriving Python scalar (``len(...)``) or an f-string-valued
  argument that the entry point does not declare in
  ``static_argnames`` — the static complement of PR 9's runtime
  recompile-storm detector: each distinct value traces a new program.
- ``vmem-budget``: a ``pallas_call`` whose BlockSpec/scratch shapes
  resolve to constants and whose per-grid-step block footprint exceeds
  the VMEM budget (default 16 MiB — the per-core VMEM size; override
  with ``--vmem-budget-mib``).  Unresolvable dims are skipped, so the
  computed footprint is a lower bound: the rule under-counts, it never
  false-positives.
"""

from __future__ import annotations

import ast
import io
import re
import tokenize
from typing import Optional

from . import callgraph
from .engine import Finding, rule

_HOST_SYNC_OK_RE = re.compile(r"#\s*host-sync-ok:(.*)$")

#: serving-path modules the host-sync rule covers
_SERVING_PREFIXES = ("filodb_tpu/query/", "filodb_tpu/parallel/",
                     "filodb_tpu/ops/")
_SERVING_FILES = ("filodb_tpu/memstore/devicestore.py",)

#: per-core VMEM (pallas guide: ~16 MB/core); --vmem-budget-mib overrides
DEFAULT_VMEM_BUDGET_BYTES = 16 * 2 ** 20
VMEM_BUDGET_BYTES = DEFAULT_VMEM_BUDGET_BYTES

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8, "complex64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "bool": 1,
}


def _in_serving_path(rel: str) -> bool:
    return rel.startswith(_SERVING_PREFIXES) or rel in _SERVING_FILES


# ---------------------------------------------------------------------------
# jit entry-point discovery (shared per-run context)
# ---------------------------------------------------------------------------


def _is_jit_marker(expr) -> bool:
    """devicewatch.jit / jax.jit / bare jit, as a decorator target or a
    callable being invoked."""
    if isinstance(expr, ast.Attribute):
        return expr.attr == "jit" and isinstance(expr.value, ast.Name) \
            and expr.value.id in ("devicewatch", "jax")
    return isinstance(expr, ast.Name) and expr.id == "jit"


def _static_argnames(call: ast.Call) -> frozenset:
    for kw in call.keywords:
        if kw.arg == "static_argnames" and isinstance(
                kw.value, (ast.Tuple, ast.List)):
            return frozenset(e.value for e in kw.value.elts
                             if isinstance(e, ast.Constant)
                             and isinstance(e.value, str))
        if kw.arg == "static_argnames" and isinstance(kw.value,
                                                      ast.Constant):
            return frozenset({kw.value.value})
    return frozenset()


def _jit_decoration(fn) -> Optional[frozenset]:
    """static_argnames if ``fn`` wears a jit decorator, else None."""
    for d in fn.decorator_list:
        if _is_jit_marker(d):
            return frozenset()
        if isinstance(d, ast.Call):
            if _is_jit_marker(d.func):
                return _static_argnames(d)
            # functools.partial(devicewatch.jit, static_argnames=...)
            f = d.func
            if isinstance(f, ast.Attribute) and f.attr == "partial" \
                    and d.args and _is_jit_marker(d.args[0]):
                return _static_argnames(d)
    return None


class _JitTable:
    """Project-wide index of jit entry points and jit factories.

    - ``entries[(rel, name)] = (FunctionDef, static_argnames)`` for
      TOP-LEVEL functions decorated with devicewatch.jit — the only
      ones reachable by the name resolution ``entry_for`` performs (a
      nested jit closure is not callable by bare name from elsewhere,
      and indexing it flat would misresolve unrelated same-named
      functions);
    - ``factories`` holds (rel, name) of top-level functions and class
      methods that BUILD jit programs (contain a jit call or a
      jit-decorated nested def — devicestore's fused programs are such
      closures — without being jit-decorated themselves): their
      results, and anything called through them
      (``_fused_progs()["grouped"](...)``), are jit programs too.
    """

    def __init__(self, project):
        self.entries: dict = {}
        self.factories: set = set()
        for m in project.modules:
            if m.tree is None:
                continue
            top = [n for n in m.tree.body
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]
            methods = [f for cls in m.tree.body
                       if isinstance(cls, ast.ClassDef)
                       for f in cls.body
                       if isinstance(f, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
            for fn in top:
                static = _jit_decoration(fn)
                if static is not None:
                    self.entries[(m.rel, fn.name)] = (fn, static)
            for fn in top + methods:
                if _jit_decoration(fn) is not None:
                    continue
                for n in ast.walk(fn):
                    if (isinstance(n, ast.Call)
                            and _is_jit_marker(n.func)) \
                            or (isinstance(n, (ast.FunctionDef,
                                               ast.AsyncFunctionDef))
                                and n is not fn
                                and _jit_decoration(n) is not None):
                        self.factories.add((m.rel, fn.name))
                        break

    def entry_for(self, call: ast.Call, rel: str, graph) -> Optional[tuple]:
        """(FunctionDef, static_argnames) when ``call`` invokes a known
        jit entry point by name (local, from-import, or module alias)."""
        f = call.func
        if isinstance(f, ast.Name):
            hit = self.entries.get((rel, f.id))
            if hit is not None:
                return hit
            tgt = graph.sym_aliases.get(rel, {}).get(f.id)
            if tgt is not None:
                return self.entries.get(tgt)
            return None
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            mod = graph.mod_aliases.get(rel, {}).get(f.value.id)
            if mod is not None:
                return self.entries.get((mod, f.attr))
        return None

    def is_factory_call(self, call: ast.Call, rel: str, graph) -> bool:
        f = call.func
        if isinstance(f, ast.Name):
            if (rel, f.id) in self.factories:
                return True
            tgt = graph.sym_aliases.get(rel, {}).get(f.id)
            return tgt is not None and tgt in self.factories
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            if f.value.id == "self" and (rel, f.attr) in self.factories:
                return True
            mod = graph.mod_aliases.get(rel, {}).get(f.value.id)
            return mod is not None and (mod, f.attr) in self.factories
        return False


def _jit_table(project) -> _JitTable:
    shared = getattr(project, "shared", None)
    if shared is None:
        return _JitTable(project)
    return shared("jit_table", _JitTable)


# ---------------------------------------------------------------------------
# host-sync — taint device results, flag undeclared readbacks
# ---------------------------------------------------------------------------


def _host_sync_kind(call: ast.Call) -> Optional[tuple]:
    """(label, synced expr) when ``call`` is a host synchronization."""
    f = call.func
    if isinstance(f, ast.Attribute):
        recv = f.value
        if f.attr in ("asarray", "array") and isinstance(recv, ast.Name) \
                and recv.id in ("np", "numpy") and call.args:
            return f"np.{f.attr}()", call.args[0]
        if f.attr == "device_get" and isinstance(recv, ast.Name) \
                and recv.id == "jax" and call.args:
            return "jax.device_get()", call.args[0]
        if f.attr == "item" and not call.args:
            return ".item()", recv
        if f.attr == "block_until_ready" and not call.args:
            return ".block_until_ready()", recv
    elif isinstance(f, ast.Name) and f.id == "float" and call.args:
        return "float()", call.args[0]
    elif isinstance(f, ast.Name) and f.id == "_fetch" and call.args:
        # devicestore's one readback helper (device wait + np.asarray
        # as stage spans): the sync is declared where it is asked for
        return "_fetch()", call.args[0]
    return None


def _root_name(expr) -> Optional[str]:
    """The Name at the root of a Name/Subscript/Attribute chain."""
    while isinstance(expr, (ast.Subscript, ast.Attribute, ast.Starred)):
        expr = expr.value
    return expr.id if isinstance(expr, ast.Name) else None


class _TaintPass:
    """One forward pass over a function body: which local names hold
    jit-program results (device values) / jit programs themselves."""

    def __init__(self, module, table, graph):
        self.m, self.table, self.graph = module, table, graph
        self.tainted: set = set()
        self.progs: set = set()

    def is_program_call(self, call: ast.Call) -> bool:
        if self.table.entry_for(call, self.m.rel, self.graph) is not None:
            return True
        f = call.func
        if isinstance(f, ast.Name) and f.id in self.progs:
            return True
        # _fused_progs()["grouped"](...) / factory(...)(...): any
        # factory call inside the callee expression makes this a
        # program invocation
        for n in ast.walk(f):
            if isinstance(n, ast.Call) \
                    and self.table.is_factory_call(n, self.m.rel,
                                                   self.graph):
                return True
        return False

    def value_taints(self, expr) -> bool:
        for n in ast.walk(expr):
            if isinstance(n, ast.Call) and self.is_program_call(n):
                return True
        root = _root_name(expr)
        return root is not None and root in self.tainted

    def note_assign(self, targets, value) -> None:
        names = []
        for t in targets:
            if isinstance(t, ast.Name):
                names.append(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                names.extend(e.id for e in t.elts
                             if isinstance(e, ast.Name))
        if not names:
            return
        if isinstance(value, ast.Call) \
                and self.table.is_factory_call(value, self.m.rel,
                                               self.graph):
            self.progs.update(names)
        elif self.value_taints(value):
            self.tainted.update(names)
        elif isinstance(value, ast.Name) and value.id in self.progs:
            self.progs.update(names)


def _own_expr_calls(stmt) -> list:
    """Call nodes in ``stmt``'s own expression subtrees — child
    statements report their own (no double-visit through parents)."""
    out = []
    stack = [c for c in ast.iter_child_nodes(stmt)
             if isinstance(c, ast.expr)]
    while stack:
        n = stack.pop()
        if isinstance(n, ast.Call):
            out.append(n)
        stack.extend(c for c in ast.iter_child_nodes(n)
                     if not isinstance(c, (ast.FunctionDef,
                                           ast.AsyncFunctionDef)))
    return out


def _own_statements(fn) -> list:
    """Statements of ``fn`` in source order, nested defs excluded
    (each FunctionDef is analyzed on its own)."""
    out = []
    stack = list(reversed(fn.body))
    while stack:
        st = stack.pop()
        out.append(st)
        kids = []
        for c in ast.iter_child_nodes(st):
            if isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(c, ast.stmt):
                kids.append(c)
            elif isinstance(c, (ast.excepthandler,)):
                kids.extend(s for s in c.body)
            elif hasattr(c, "body") and isinstance(getattr(c, "body"),
                                                   list):
                kids.extend(s for s in c.body
                            if isinstance(s, ast.stmt))
        stack.extend(reversed(kids))
    return out


def _annotations(module) -> dict:
    """{line: reason-or-None} for ``# host-sync-ok`` comments — real
    COMMENT tokens only (a docstring quoting the syntax is not an
    annotation), the same discipline as the engine's suppression
    scanner and # lock-order:."""
    out: dict = {}
    if "host-sync-ok" not in module.src:
        return out
    try:
        toks = tokenize.generate_tokens(io.StringIO(module.src).readline)
        comments = [(t.start[0], t.string) for t in toks
                    if t.type == tokenize.COMMENT]
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return out
    for i, text in comments:
        m = _HOST_SYNC_OK_RE.search(text)
        if m is not None:
            reason = m.group(1).strip().lstrip("—-: ").strip()
            out[i] = reason or None
    return out


def _scan_host_syncs(project):
    """Shared worker for host-sync + host-sync-annotation: findings per
    rule, computed in one pass."""

    def _build(p):
        graph = callgraph.build(p)
        table = _jit_table(p)
        syncs, dangling = [], []
        for m in p.modules:
            if m.tree is None or not _in_serving_path(m.rel):
                continue
            notes = _annotations(m)
            used_lines: set = set()
            for fn in m.nodes:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                tp = _TaintPass(m, table, graph)
                for st in _own_statements(fn):
                    for n in _own_expr_calls(st):
                        kind = _host_sync_kind(n)
                        if kind is None:
                            continue
                        label, target = kind
                        if not tp.value_taints(target):
                            continue
                        used_lines.add(n.lineno)
                        if notes.get(n.lineno):
                            continue       # declared, with a reason
                        syncs.append(Finding(
                            "host-sync", m.rel, n.lineno,
                            f"{label} on the result of a devicewatch-"
                            f"jit program in the serving path — this "
                            f"readback stalls the device pipeline for "
                            f"a host round trip and silently demotes "
                            f"the fast path; batch it, keep the value "
                            f"on device, or declare it "
                            f"'# host-sync-ok: <reason>'"))
                    if isinstance(st, ast.Assign):
                        tp.note_assign(st.targets, st.value)
                    elif isinstance(st, ast.AnnAssign) \
                            and st.value is not None:
                        tp.note_assign([st.target], st.value)
            for line, reason in notes.items():
                if reason is None:
                    dangling.append(Finding(
                        "host-sync-annotation", m.rel, line,
                        "'# host-sync-ok' without a reason — append "
                        "': <why this readback is the design>'"))
                elif line not in used_lines:
                    dangling.append(Finding(
                        "host-sync-annotation", m.rel, line,
                        "'# host-sync-ok' on a line with no detected "
                        "host sync of a jit-program result — delete "
                        "it (stale annotations hide future "
                        "regressions)"))
        return syncs, dangling

    shared = getattr(project, "shared", None)
    return _build(project) if shared is None \
        else shared("host_sync_scan", _build)


@rule("host-sync", scope="project",
      doc="undeclared host syncs of jit results in the serving path")
def host_sync(project):
    return _scan_host_syncs(project)[0]


@rule("host-sync-annotation", scope="project",
      doc="# host-sync-ok annotations that are bare or stale")
def host_sync_annotation(project):
    return _scan_host_syncs(project)[1]


# ---------------------------------------------------------------------------
# recompile-hazard — per-call-varying traced args at jit call sites
# ---------------------------------------------------------------------------


def _contains_len_call(expr) -> bool:
    return any(isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
               and n.func.id == "len" for n in ast.walk(expr))


def _hazard(expr, varying: set) -> Optional[str]:
    if _contains_len_call(expr):
        return "a len(...)-derived Python scalar"
    if isinstance(expr, ast.JoinedStr):
        return "an f-string"
    if isinstance(expr, ast.Name) and expr.id in varying:
        return f"'{expr.id}' (bound to a len()/f-string value above)"
    return None


@rule("recompile-hazard", scope="project",
      doc="jit call sites passing varying values not declared static")
def recompile_hazard(project):
    graph = callgraph.build(project)
    table = _jit_table(project)
    findings = []
    for m in project.modules:
        if m.tree is None or not m.rel.startswith("filodb_tpu/"):
            continue
        for fn in m.nodes:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            varying: set = set()
            for st in _own_statements(fn):
                for n in _own_expr_calls(st):
                    hit = table.entry_for(n, m.rel, graph)
                    if hit is None:
                        continue
                    entry, static = hit
                    pos_names = [a.arg for a in entry.args.args]
                    for i, a in enumerate(n.args):
                        name = pos_names[i] if i < len(pos_names) \
                            else None
                        if name in static:
                            continue
                        why = _hazard(a, varying)
                        if why is not None:
                            findings.append(_hazard_finding(
                                m.rel, a.lineno, entry.name, name,
                                why))
                    for kw in n.keywords:
                        if kw.arg in static:
                            continue
                        why = _hazard(kw.value, varying)
                        if why is not None:
                            findings.append(_hazard_finding(
                                m.rel, kw.value.lineno, entry.name,
                                kw.arg, why))
                if isinstance(st, ast.Assign):
                    for t in st.targets:
                        if isinstance(t, ast.Name) and (
                                _contains_len_call(st.value)
                                or isinstance(st.value, ast.JoinedStr)):
                            varying.add(t.id)
    return findings


def _hazard_finding(rel, line, entry, argname, why) -> Finding:
    arg = f"argument {argname!r}" if argname else "a positional argument"
    return Finding(
        "recompile-hazard", rel, line,
        f"{entry}() is a jit entry point but {arg} receives {why} "
        f"without being declared in static_argnames — every distinct "
        f"value keys a fresh trace/compile (the recompile-storm shape "
        f"PR 9 detects at runtime); declare it static if its values "
        f"are bounded, or hoist it out of the traced signature")


# ---------------------------------------------------------------------------
# vmem-budget — pallas_call per-block byte footprint
# ---------------------------------------------------------------------------


def _const_env(module) -> dict:
    """{name: int} for names assigned EXACTLY one constant-int value
    anywhere in the module (module level or function-local)."""
    env: dict = {}
    poisoned: set = set()
    for n in module.nodes:
        if not isinstance(n, ast.Assign):
            continue
        for t in n.targets:
            if not isinstance(t, ast.Name):
                continue
            if isinstance(n.value, ast.Constant) \
                    and isinstance(n.value.value, int):
                if t.id in env and env[t.id] != n.value.value:
                    poisoned.add(t.id)
                env[t.id] = n.value.value
            else:
                poisoned.add(t.id)
    for name in poisoned:
        env.pop(name, None)
    return env


def _resolve_dim(expr, env: dict) -> Optional[int]:
    if isinstance(expr, ast.Constant) and isinstance(expr.value, int):
        return expr.value
    if isinstance(expr, ast.Name):
        return env.get(expr.id)
    if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.USub):
        v = _resolve_dim(expr.operand, env)
        return None if v is None else -v
    if isinstance(expr, ast.BinOp):
        lo = _resolve_dim(expr.left, env)
        ro = _resolve_dim(expr.right, env)
        if lo is None or ro is None:
            return None
        try:
            if isinstance(expr.op, ast.Add):
                return lo + ro
            if isinstance(expr.op, ast.Sub):
                return lo - ro
            if isinstance(expr.op, ast.Mult):
                return lo * ro
            if isinstance(expr.op, ast.FloorDiv):
                return lo // ro
            if isinstance(expr.op, ast.Pow):
                return lo ** ro
        except (ZeroDivisionError, OverflowError):
            return None
    return None


def _dtype_bytes(expr) -> Optional[int]:
    name = None
    if isinstance(expr, ast.Attribute):
        name = expr.attr
    elif isinstance(expr, ast.Name):
        name = expr.id
    elif isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        name = expr.value
    return _DTYPE_BYTES.get(name)


def _block_bytes(shape_expr, env, elem_bytes) -> Optional[int]:
    if not isinstance(shape_expr, (ast.Tuple, ast.List)):
        return None
    total = elem_bytes
    for dim in shape_expr.elts:
        v = _resolve_dim(dim, env)
        if v is None or v <= 0:
            return None
        total *= v
    return total


def _iter_specs(expr):
    """Flatten an in_specs/out_specs expression into BlockSpec calls."""
    if expr is None:
        return
    if isinstance(expr, (ast.Tuple, ast.List)):
        for e in expr.elts:
            yield from _iter_specs(e)
    elif isinstance(expr, ast.Call):
        f = expr.func
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None)
        if name == "BlockSpec":
            yield expr


def _out_dtype_bytes(call: ast.Call) -> int:
    """Element size from out_shape's ShapeDtypeStruct dtype; f32 when
    unresolvable (the repo's kernels are f32-dominant)."""
    for kw in call.keywords:
        if kw.arg != "out_shape":
            continue
        for n in ast.walk(kw.value):
            if isinstance(n, ast.Call):
                f = n.func
                nm = f.attr if isinstance(f, ast.Attribute) else (
                    f.id if isinstance(f, ast.Name) else None)
                if nm == "ShapeDtypeStruct" and len(n.args) >= 2:
                    b = _dtype_bytes(n.args[1])
                    if b is not None:
                        return b
    return 4


@rule("vmem-budget",
      doc="pallas_call block footprints exceeding the VMEM budget")
def vmem_budget(module):
    if "pallas_call" not in module.src:
        return []
    env = _const_env(module)
    findings = []
    for call in module.nodes:
        if not isinstance(call, ast.Call):
            continue
        f = call.func
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None)
        if name != "pallas_call":
            continue
        elem = _out_dtype_bytes(call)
        total = 0
        parts = []
        for kw in call.keywords:
            if kw.arg in ("in_specs", "out_specs"):
                for spec in _iter_specs(kw.value):
                    shape = spec.args[0] if spec.args else None
                    b = _block_bytes(shape, env, elem)
                    if b is not None:
                        total += b
                        parts.append(f"{kw.arg} block {b // 1024} KiB")
            elif kw.arg == "scratch_shapes":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Call) and n.args:
                        eb = _dtype_bytes(n.args[1]) \
                            if len(n.args) >= 2 else elem
                        b = _block_bytes(n.args[0], env, eb or elem)
                        if b is not None:
                            total += b
                            parts.append(
                                f"scratch {b // 1024} KiB")
        if total > VMEM_BUDGET_BYTES:
            findings.append(Finding(
                "vmem-budget", module.rel, call.lineno,
                f"pallas_call blocks resolve to {total / 2**20:.1f} "
                f"MiB of VMEM per grid step "
                f"({'; '.join(parts)}), over the "
                f"{VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget — the "
                f"kernel will fail to fit at lowering (or spill); "
                f"shrink the BlockSpec tiles or raise "
                f"--vmem-budget-mib if this device has more VMEM"))
    return findings
