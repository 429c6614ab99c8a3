"""filolint engine + the three semantic analyses (ISSUE 8).

Covers:

- engine mechanics: justification-required suppressions, stale-
  suppression detection, unknown rules, meta-rule unsuppressibility;
- a generalized positive/negative fixture over ALL rules (the old
  per-lint ``*_lint_catches_*`` pattern, one table) including the
  seeded PR 11/12 bug shapes (blocking peer POST under a held lock,
  tenant-gauge mutation off the export lock, stall-machine state);
- lock-discipline specifics: ``# guarded-by:`` / ``# holds-lock:``
  annotations, the ``*_locked`` naming convention, Condition aliasing,
  deferred (lambda / nested def) bodies;
- the tier-1 gate: zero unsuppressed findings over filodb_tpu/ under a
  10s wall-clock budget, ``--json`` output shaped for CI, nonzero exit
  on a violation, and the delete-any-suppression / re-introduce-the-
  fixed-bug regressions the acceptance criteria name.
"""

import json
import pathlib
import time

import pytest

import filodb_tpu.analysis as A
from filodb_tpu.analysis.__main__ import main as lint_main

REPO = pathlib.Path(__file__).resolve().parents[1]
PKG = REPO / "filodb_tpu"


def _fake(src, rules, rel="filodb_tpu/fake.py", **kw):
    return A.unsuppressed(A.run_source(src, rules=rules, rel=rel, **kw))


# ---------------------------------------------------------------------------
# engine: suppression discipline
# ---------------------------------------------------------------------------

_BAD_SENTINEL = (
    "def f(self, buf):\n"
    "    self._lib.dd_decode(buf, 1, 2, 3, None, 0){}\n"
)


def test_suppression_needs_matching_rule_and_reason():
    # justified suppression of the right rule: silent
    src = _BAD_SENTINEL.format(
        "  # filolint: disable=decode-sentinel — synthetic input")
    fs = A.run_source(src, rules=["decode-sentinel"])
    assert A.unsuppressed(fs) == []
    sup = [f for f in fs if f.suppressed]
    assert len(sup) == 1 and sup[0].suppress_reason == "synthetic input"


def test_suppression_without_reason_is_an_error():
    src = _BAD_SENTINEL.format("  # filolint: disable=decode-sentinel")
    got = _fake(src, ["decode-sentinel"])
    rules = {f.rule for f in got}
    # the original finding stays visible AND the bare disable is flagged
    assert "decode-sentinel" in rules
    assert A.engine.SUPPRESSION_SYNTAX in rules


def test_stale_suppression_is_an_error():
    src = ("x = 1  # filolint: disable=decode-sentinel — nothing actually "
           "fires here\n")
    got = _fake(src, ["decode-sentinel"])
    assert len(got) == 1 and got[0].rule == A.engine.STALE_SUPPRESSION
    assert "never fires" in got[0].message


def test_stale_only_relative_to_selected_rules():
    """A --rules subset must not condemn other rules' suppressions."""
    src = ("x = 1  # filolint: disable=decode-sentinel — pending\n")
    got = _fake(src, ["timed-handler"])      # decode-sentinel did not run
    assert got == []


def test_unknown_rule_in_disable_is_an_error():
    src = "x = 1  # filolint: disable=no-such-rule — whatever\n"
    got = _fake(src, ["decode-sentinel"])
    assert len(got) == 1 and "unknown rule" in got[0].message


def test_meta_rules_cannot_be_suppressed():
    src = ("x = 1  # filolint: disable=stale-suppression — nice try\n")
    got = _fake(src, ["decode-sentinel"])
    assert any("cannot be suppressed" in f.message for f in got)


def test_multi_rule_disable_comment():
    src = (
        "import urllib.request\n"
        "class C:\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            urllib.request.urlopen(u)  "
        "# filolint: disable=blocking-under-lock,deadline-threading "
        "— test double: both rules fire on this line by design\n"
    )
    fs = A.run_source(src, rules=["blocking-under-lock",
                                  "deadline-threading"])
    assert A.unsuppressed(fs) == []
    assert sum(1 for f in fs if f.suppressed) == 2


def test_unparseable_module_is_reported():
    got = _fake("def broken(:\n", ["decode-sentinel"])
    assert len(got) == 1 and "unparseable" in got[0].message


def test_docstring_mention_is_not_a_directive():
    src = '"""Docs may show # filolint: disable=decode-sentinel — x."""\n'
    assert _fake(src, ["decode-sentinel"]) == []


# ---------------------------------------------------------------------------
# one table of positive/negative snippets for every rule (the old
# *_lint_catches_* pattern, generalized)
# ---------------------------------------------------------------------------

RULE_CASES = [
    ("decode-sentinel",
     "def f(self, buf):\n    self._lib.dd_decode(buf, 1)\n",
     "def f(self, buf):\n    got = self._lib.dd_decode(buf, 1)\n"
     "    if got < 0:\n        raise ValueError\n",
     "sentinel", {}),
    ("timed-handler",
     "class FiloHttpServer:\n"
     "    def _route(self, p, q):\n        return self._dark(q)\n"
     "    def _dark(self, q):\n        return 200, {}\n",
     "class FiloHttpServer:\n"
     "    def _route(self, p, q):\n        return self._lit(q)\n"
     "    @_timed('lit')\n"
     "    def _lit(self, q):\n        return 200, {}\n",
     "histogram", {}),
    ("interpret-coverage",
     "def new_kernel(x, interpret=False):\n    return x\n",
     "def new_kernel(x, interpret=False):\n    return x\n",
     "interpret", {"rel": "filodb_tpu/ops/fake.py",
                   "good_kw": {"test_sources":
                               ["y = new_kernel(a, interpret=True)"]},
                   "bad_kw": {"test_sources": ["z = 1"]}}),
    ("device-put-ledger",
     "import jax\nx = jax.device_put(a, d)\n",
     "from filodb_tpu.utils.devicewatch import LEDGER\n"
     "x = LEDGER.device_put(a, d, owner='o', fmt='dense')\n",
     "ledger", {}),
    ("admission-routing",
     "class FiloHttpServer:\n"
     "    def _exec(self, b, plan):\n"
     "        ep = b.planner.materialize(plan, q)\n"
     "        return ep.execute(ctx)\n",
     "class FiloHttpServer:\n"
     "    def _exec(self, b, plan):\n"
     "        ep = b.planner.materialize(plan, q)\n"
     "        with self._admit(b, ep, q):\n"
     "            return ep.execute(ctx)\n",
     "_admit", {}),
    ("deadline-threading",
     "import urllib.request\n"
     "class MyPlanDispatcher:\n"
     "    def dispatch(self):\n"
     "        urllib.request.urlopen(req, timeout=60.0)\n",
     "import urllib.request\n"
     "class MyPlanDispatcher:\n"
     "    def dispatch(self):\n"
     "        remaining_s = deadline.budget_timeout_s(q, 60.0)\n"
     "        urllib.request.urlopen(req, timeout=remaining_s)\n",
     "deadline", {}),
    ("metric-doc",
     "m = REG.counter('filodb_brand_new_total', 'h')\n",
     "m = REG.counter('filodb_query_request_seconds', 'h')\n",
     "observability.md",
     {"good_kw": {"doc_text": "| `filodb_query_*` | `request_seconds` |"},
      "bad_kw": {"doc_text": "| `filodb_query_*` | `request_seconds` |"}}),
    ("admin-endpoint-documented",
     # same dispatch arm both ways; only the doc table differs — the
     # rule reads the router's parts[i] == "..." compares, never
     # "/admin/..." string literals (the router has none)
     "class FiloHttpServer:\n"
     "    def _route(self, path, params):\n"
     "        parts = path.split('/')\n"
     "        if len(parts) == 2 and parts[0] == 'admin' \\\n"
     "                and parts[1] == 'darkroute':\n"
     "            return self._dark(params)\n",
     "class FiloHttpServer:\n"
     "    def _route(self, path, params):\n"
     "        parts = path.split('/')\n"
     "        if len(parts) == 2 and parts[0] == 'admin' \\\n"
     "                and parts[1] == 'darkroute':\n"
     "            return self._dark(params)\n",
     "http_api.md",
     {"rel": "filodb_tpu/http/server.py",
      "good_kw": {"api_doc_text":
                  "| `GET /admin/darkroute` | dark corner |"},
      "bad_kw": {"api_doc_text":
                 "| `GET /admin/insights` | documented elsewhere |"}}),
    ("evaluator-workload",
     # a background evaluator minting query identity without a
     # workload class or deadline — invisible ambient-priority load
     "class BackgroundEvaluator:\n"
     "    def tick(self):\n"
     "        qctx = QueryContext(submit_time_ms=1)\n"
     "        ep = self.planner.materialize(plan, qctx)\n"
     "        return ep.execute(ctx)\n",
     "from filodb_tpu.workload import deadline as wdl\n"
     "class BackgroundEvaluator:\n"
     "    def tick(self):\n"
     "        qctx = wdl.mint(QueryContext(submit_time_ms=1,\n"
     "                                     priority='rules'))\n"
     "        ep = self.planner.materialize(plan, qctx)\n"
     "        return ep.execute(ctx)\n",
     "priority", {}),
    ("kernel-timer-coverage",
     # the kernel-timer ledger keys on program=; the __name__ fallback
     # forks the ledger row on any rename (ISSUE 15)
     "from filodb_tpu.utils import devicewatch\n"
     "staged = devicewatch.jit(fn)\n",
     "from filodb_tpu.utils import devicewatch\n"
     "staged = devicewatch.jit(fn, program='m.stage')\n",
     "program=", {}),
    ("replica-routing",
     "class MyPlanDispatcher:\n"
     "    def dispatch(self, plan, ctx):\n"
     "        return self.mapper.replica_nodes(plan.shard)[0]\n",
     "class MyPlanDispatcher:\n"
     "    def dispatch(self, plan, ctx):\n"
     "        return self.replica_set.pick(plan.shard)[0]\n",
     "ReplicaSet.pick", {}),
    ("bounded-cache",
     # the PR 11 gateway-memo stampede shape: guarded read + keyed
     # write, nothing ever evicts
     "class SeriesMemo:\n"
     "    def __init__(self):\n"
     "        self._memo = {}\n"
     "    def lookup(self, key):\n"
     "        got = self._memo.get(key)\n"
     "        if got is None:\n"
     "            got = self._memo[key] = self._compute(key)\n"
     "        return got\n",
     "class SeriesMemo:\n"
     "    def __init__(self):\n"
     "        self._memo = {}\n"
     "    def lookup(self, key):\n"
     "        got = self._memo.get(key)\n"
     "        if got is None:\n"
     "            if len(self._memo) > 1000:\n"
     "                self._memo.clear()\n"
     "            got = self._memo[key] = self._compute(key)\n"
     "        return got\n",
     "eviction bound", {"rel": "filodb_tpu/gateway/fake.py"}),
    # --- the three NEW analyses, seeded with the PR 11/12 bug shapes ---
    ("lock-discipline",
     # the _set_tenant_gauges shape: rows mutated off the export lock
     "class TenantGauges:\n"
     "    def __init__(self):\n"
     "        self._rows = {}\n"
     "    def sample(self):\n"
     "        with _EXPORT_LOCK:\n"
     "            self._rows['a'] = 1\n"
     "    def report(self):\n"
     "        with _EXPORT_LOCK:\n"
     "            self._rows.pop('a', None)\n"
     "    def clobber(self):\n"
     "        self._rows.clear()\n",
     "class TenantGauges:\n"
     "    def __init__(self):\n"
     "        self._rows = {}\n"
     "    def sample(self):\n"
     "        with _EXPORT_LOCK:\n"
     "            self._rows['a'] = 1\n"
     "    def report(self):\n"
     "        with _EXPORT_LOCK:\n"
     "            self._rows.pop('a', None)\n"
     "    def clobber(self):\n"
     "        with _EXPORT_LOCK:\n"
     "            self._rows.clear()\n",
     "does not hold it", {}),
    ("blocking-under-lock",
     # the ReplicaFanout wedge: a blocking peer POST inside the lock
     "import urllib.request\n"
     "class ReplicaFanout:\n"
     "    def publish(self, container):\n"
     "        with self._lock:\n"
     "            urllib.request.urlopen(req, timeout=self.timeout_s)\n",
     "import urllib.request\n"
     "class ReplicaFanout:\n"
     "    def publish(self, container):\n"
     "        with self._lock:\n"
     "            lanes = list(self._lanes)\n"
     "        urllib.request.urlopen(req, timeout=self.timeout_s)\n",
     "convoy", {}),
    ("resource-lifecycle",
     "class T:\n"
     "    def start(self):\n"
     "        g = registry.gauge('x')\n"
     "        g.set_fn(self._sample, shard=1)\n",
     "class T:\n"
     "    def start(self):\n"
     "        g = registry.gauge('x')\n"
     "        g.set_fn(self._sample, shard=1)\n"
     "    def close(self):\n"
     "        registry.gauge('x').remove(shard=1)\n",
     "Gauge.remove", {}),
    # --- ISSUE 10: lock order + device discipline ---
    ("lock-order-cycle",
     # the shard/index AB/BA shape: freeze takes shard->index while
     # evict takes index->shard — two threads deadlock
     "class TimeSeriesShard:\n"
     "    def freeze(self):\n"
     "        with self._shard_lock:\n"
     "            with self._index_lock:\n"
     "                pass\n"
     "    def evict(self):\n"
     "        with self._index_lock:\n"
     "            with self._shard_lock:\n"
     "                pass\n",
     "class TimeSeriesShard:\n"
     "    def freeze(self):\n"
     "        with self._shard_lock:\n"
     "            with self._index_lock:\n"
     "                pass\n"
     "    def evict(self):\n"
     "        with self._shard_lock:\n"
     "            with self._index_lock:\n"
     "                pass\n",
     "deadlock", {}),
    ("lock-order-inversion",
     "class Part:\n"
     "    def __init__(self):\n"
     "        # lock-order: _encode_lock < _buf_lock\n"
     "        self._buf_lock = mk()\n"
     "    def bad(self):\n"
     "        with self._buf_lock:\n"
     "            with self._encode_lock:\n"
     "                pass\n",
     "class Part:\n"
     "    def __init__(self):\n"
     "        # lock-order: _encode_lock < _buf_lock\n"
     "        self._buf_lock = mk()\n"
     "    def good(self):\n"
     "        with self._encode_lock:\n"
     "            with self._buf_lock:\n"
     "                pass\n",
     "declares", {}),
    ("host-sync",
     "import numpy as np\n"
     "from filodb_tpu.utils import devicewatch\n"
     "@devicewatch.jit\n"
     "def prog(x):\n"
     "    return x\n"
     "def serve(x):\n"
     "    out = prog(x)\n"
     "    return np.asarray(out)\n",
     "import numpy as np\n"
     "from filodb_tpu.utils import devicewatch\n"
     "@devicewatch.jit\n"
     "def prog(x):\n"
     "    return x\n"
     "def serve(x):\n"
     "    out = prog(x)\n"
     "    return np.asarray(out)  # host-sync-ok: the one designed "
     "readback for serialization\n",
     "readback", {"rel": "filodb_tpu/query/fake.py"}),
    ("host-sync-annotation",
     # an annotation on a line with no detected sync is stale
     "x = 1  # host-sync-ok: nothing here\n",
     "import numpy as np\n"
     "from filodb_tpu.utils import devicewatch\n"
     "@devicewatch.jit\n"
     "def prog(x):\n"
     "    return x\n"
     "def serve(x):\n"
     "    out = prog(x)\n"
     "    return np.asarray(out)  # host-sync-ok: designed readback\n",
     "stale", {"rel": "filodb_tpu/query/fake.py"}),
    ("recompile-hazard",
     # a jit call site keyed on a Python len(...): every distinct
     # series count traces a fresh program (the PR 9 storm shape)
     "from filodb_tpu.utils import devicewatch\n"
     "@devicewatch.jit\n"
     "def prog(x, nrows):\n"
     "    return x\n"
     "def serve(rows, x):\n"
     "    return prog(x, len(rows))\n",
     "import functools\n"
     "from filodb_tpu.utils import devicewatch\n"
     "@functools.partial(devicewatch.jit, static_argnames=('nrows',))\n"
     "def prog(x, *, nrows):\n"
     "    return x\n"
     "def serve(rows, x):\n"
     "    return prog(x, nrows=len(rows))\n",
     "static_argnames", {}),
    ("vmem-budget",
     # 2 x 4096x4096 f32 blocks = 128 MiB per grid step
     "import jax\n"
     "import jax.numpy as jnp\n"
     "from jax.experimental import pallas as pl\n"
     "def kern(x_ref, o_ref):\n"
     "    o_ref[...] = x_ref[...]\n"
     "def big(x):\n"
     "    return pl.pallas_call(\n"
     "        kern,\n"
     "        out_shape=jax.ShapeDtypeStruct((4096, 4096), jnp.float32),\n"
     "        in_specs=[pl.BlockSpec((4096, 4096), lambda i: (0, 0))],\n"
     "        out_specs=pl.BlockSpec((4096, 4096), lambda i: (0, 0)),\n"
     "    )(x)\n",
     "import jax\n"
     "import jax.numpy as jnp\n"
     "from jax.experimental import pallas as pl\n"
     "def kern(x_ref, o_ref):\n"
     "    o_ref[...] = x_ref[...]\n"
     "def small(x):\n"
     "    return pl.pallas_call(\n"
     "        kern,\n"
     "        out_shape=jax.ShapeDtypeStruct((4096, 4096), jnp.float32),\n"
     "        in_specs=[pl.BlockSpec((256, 1024), lambda i: (i, 0))],\n"
     "        out_specs=pl.BlockSpec((256, 1024), lambda i: (i, 0)),\n"
     "    )(x)\n",
     "VMEM", {}),
    ("batch-admission-discipline",
     # a group executor stacking members and launching the vmapped
     # program without consulting permits or deadline budgets
     "def launch_group(self, g, batch_launch):\n"
     "    row0s = [m.row0 for m in g.members]\n"
     "    return batch_launch(row0s)\n",
     "def launch_group(self, g, batch_launch):\n"
     "    live = [m for m in g.members\n"
     "            if not m.qctx.admission_permit.released\n"
     "            and remaining_ms(m.qctx) > 0]\n"
     "    return batch_launch([m.row0 for m in live])\n",
     "admission_permit", {}),
]


@pytest.mark.parametrize(
    "rule,bad,good,match,extra",
    RULE_CASES, ids=[c[0] for c in RULE_CASES])
def test_rule_fires_on_bad_and_accepts_good(rule, bad, good, match, extra):
    rel = extra.get("rel", "filodb_tpu/fake.py")
    got = _fake(bad, [rule], rel=rel, **extra.get("bad_kw", {}))
    assert got, f"{rule}: did not fire on the bad shape"
    assert all(f.rule == rule for f in got)
    assert any(match in f.message for f in got), \
        f"{rule}: message lacks {match!r}: {got[0].message}"
    assert _fake(good, [rule], rel=rel, **extra.get("good_kw", {})) == [], \
        f"{rule}: false positive on the good shape"


# ---------------------------------------------------------------------------
# lock-discipline specifics
# ---------------------------------------------------------------------------


def test_guarded_by_annotation_flags_reads_and_writes():
    src = (
        "class StallMachine:\n"
        "    def __init__(self):\n"
        "        self._stall = {}  # guarded-by: _lock\n"
        "    def sample(self):\n"
        "        with self._lock:\n"
        "            self._stall['k'] = 1\n"
        "    def peek(self):\n"
        "        return self._stall.get('k')\n"
    )
    got = _fake(src, ["lock-discipline"])
    assert len(got) == 1 and "read here without holding" in got[0].message
    fixed = src.replace(
        "        return self._stall.get('k')\n",
        "        with self._lock:\n"
        "            return self._stall.get('k')\n")
    assert _fake(fixed, ["lock-discipline"]) == []


def test_bounded_cache_scoped_to_serving_paths():
    """The same unbounded memo outside the serving prefixes (analysis
    tooling, tests, utils) is not a stampede surface and stays silent."""
    src = ("class M:\n"
           "    def __init__(self):\n"
           "        self._memo = {}\n"
           "    def get(self, k):\n"
           "        if k not in self._memo:\n"
           "            self._memo[k] = 1\n"
           "        return self._memo[k]\n")
    assert _fake(src, ["bounded-cache"],
                 rel="filodb_tpu/gateway/fake.py") != []
    assert _fake(src, ["bounded-cache"],
                 rel="filodb_tpu/analysis/fake.py") == []


def test_bounded_cache_accepts_evict_helper_and_module_memos():
    """Handing the memo to an evict/prune helper (the gateway
    evict_memo_half shape) is a bound; module-level memos are checked
    with the same shape rules."""
    helper = ("def lookup(self, k):\n"
              "    got = self._memo.get(k)\n"
              "    if got is None:\n"
              "        evict_memo_half(self._memo)\n"
              "        got = self._memo[k] = compute(k)\n"
              "    return got\n")
    src = ("class M:\n"
           "    def __init__(self):\n"
           "        self._memo = {}\n" + "    " +
           helper.replace("\n", "\n    ").rstrip() + "\n")
    assert _fake(src, ["bounded-cache"],
                 rel="filodb_tpu/gateway/fake.py") == []
    mod = ("_MEMO = {}\n"
           "def lookup(k):\n"
           "    got = _MEMO.get(k)\n"
           "    if got is None:\n"
           "        got = _MEMO[k] = compute(k)\n"
           "    return got\n")
    got = _fake(mod, ["bounded-cache"], rel="filodb_tpu/query/fake.py")
    assert got and "module scope" in got[0].message


def test_kernel_timer_coverage_unique_across_modules():
    """Two entry points sharing one program name merge their device-time
    ledger rows — the duplicate check is whole-program (ISSUE 15)."""
    a = ("from filodb_tpu.utils import devicewatch\n"
         "f = devicewatch.jit(fn, program='grid.x')\n")
    b = ("from filodb_tpu.utils import devicewatch\n"
         "g = devicewatch.jit(fn2, program='grid.x')\n")
    got = A.unsuppressed(A.run_sources(
        {"filodb_tpu/ops/a.py": a, "filodb_tpu/ops/b.py": b},
        rules=["kernel-timer-coverage"]))
    assert len(got) == 1 and "duplicate" in got[0].message \
        and "ops/a.py" in got[0].message
    got = A.unsuppressed(A.run_sources(
        {"filodb_tpu/ops/a.py": a,
         "filodb_tpu/ops/b.py": b.replace("'grid.x'", "'grid.y'")},
        rules=["kernel-timer-coverage"]))
    assert got == []


def test_kernel_timer_coverage_forms():
    """Bare decorators and partial() decorators without program=, and
    computed (non-literal) names, all fire; devicewatch.py itself (the
    wrapper's home, whose docstring/recursion spell jit bare) is
    exempt."""
    bare = ("from filodb_tpu.utils import devicewatch\n"
            "@devicewatch.jit\n"
            "def prog(x):\n    return x\n")
    got = _fake(bare, ["kernel-timer-coverage"])
    assert got and "program=" in got[0].message
    partial_bad = ("import functools\n"
                   "from filodb_tpu.utils import devicewatch\n"
                   "@functools.partial(devicewatch.jit,\n"
                   "                   static_argnames=('q',))\n"
                   "def prog(x, *, q):\n    return x\n")
    assert _fake(partial_bad, ["kernel-timer-coverage"])
    partial_ok = partial_bad.replace(
        "static_argnames=('q',)",
        "program='ops.prog', static_argnames=('q',)")
    assert _fake(partial_ok, ["kernel-timer-coverage"]) == []
    computed = ("from filodb_tpu.utils import devicewatch\n"
                "f = devicewatch.jit(fn, program='pfx.' + name)\n")
    got = _fake(computed, ["kernel-timer-coverage"])
    assert got and "string literal" in got[0].message
    assert _fake(bare, ["kernel-timer-coverage"],
                 rel="filodb_tpu/utils/devicewatch.py") == []


def test_dangling_guarded_by_annotation_is_an_error():
    """A guarded-by comment that binds to no attribute assignment must
    fail loudly, not silently disarm the race detector."""
    src = (
        "class C:\n"
        "    def __init__(self):\n"
        "        pass  # guarded-by: _lock\n"
    )
    got = _fake(src, ["lock-discipline"])
    assert len(got) == 1 and "binds to nothing" in got[0].message


def test_holds_lock_annotation_and_locked_suffix():
    src = (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._m = {}  # guarded-by: _lock\n"
        "    def a(self):\n"
        "        with self._lock:\n"
        "            self._apply_locked()\n"
        "    def _apply_locked(self):\n"
        "        self._m['x'] = 1\n"
        "    def _sweep(self):  # holds-lock: _lock\n"
        "        self._m.clear()\n"
    )
    assert _fake(src, ["lock-discipline"]) == []


def test_condition_aliases_its_lock():
    src = (
        "import threading\n"
        "class Q:\n"
        "    def __init__(self):\n"
        "        self._pending = []  # guarded-by: _lock\n"
        "        self._lock = threading.Lock()\n"
        "        self._cv = threading.Condition(self._lock)\n"
        "    def put(self, x):\n"
        "        with self._cv:\n"
        "            self._pending.append(x)\n"
        "    def drain(self):\n"
        "        with self._lock:\n"
        "            self._pending.clear()\n"
    )
    assert _fake(src, ["lock-discipline"]) == []


def test_deferred_bodies_do_not_inherit_the_lock():
    """A lambda/def registered under a lock runs later WITHOUT it —
    the walker must not treat its body as locked (a blocking call in a
    set_fn callback registered under a lock is fine)."""
    src = (
        "import urllib.request\n"
        "class C:\n"
        "    def start(self):\n"
        "        with self._lock:\n"
        "            self._cb = lambda: urllib.request.urlopen(u)\n"
    )
    assert _fake(src, ["blocking-under-lock"]) == []


def test_blocking_propagates_through_local_helpers():
    src = (
        "import time\n"
        "class C:\n"
        "    def outer(self):\n"
        "        with self._lock:\n"
        "            self._hop1()\n"
        "    def _hop1(self):\n"
        "        self._hop2()\n"
        "    def _hop2(self):\n"
        "        time.sleep(1)\n"
    )
    got = _fake(src, ["blocking-under-lock"])
    assert len(got) == 1
    assert "via _hop1 -> _hop2" in got[0].message


def test_future_result_and_thread_join_under_lock():
    src = (
        "class C:\n"
        "    def a(self, fut, t):\n"
        "        with self._lock:\n"
        "            x = fut.result(timeout=5)\n"
        "            t.join()\n"
        "    def b(self, parts):\n"
        "        with self._lock:\n"
        "            return ','.join(parts)\n"     # str.join: not blocking
    )
    got = _fake(src, ["blocking-under-lock"])
    assert len(got) == 2


def test_lifecycle_periodic_thread_and_finalize_and_pool():
    thread_bad = (
        "class S:\n"
        "    def start(self):\n"
        "        self._loop = PeriodicThread(self.tick, 5.0)\n"
    )
    got = _fake(thread_bad, ["resource-lifecycle"])
    assert len(got) == 1 and "PeriodicThread" in got[0].message
    thread_good = thread_bad + (
        "    def close(self):\n"
        "        self._loop.stop()\n")
    assert _fake(thread_good, ["resource-lifecycle"]) == []

    fin_bad = (
        "import weakref\n"
        "class L:\n"
        "    def track(self, arr):\n"
        "        weakref.finalize(arr, self._cb, 1)\n"
    )
    got = _fake(fin_bad, ["resource-lifecycle"])
    assert len(got) == 1 and "finalize" in got[0].message
    fin_good = fin_bad + (
        "    def untrack(self, key):\n"
        "        self._fins.pop(key, None)\n")
    assert _fake(fin_good, ["resource-lifecycle"]) == []

    pool_bad = (
        "class Sh:\n"
        "    def start(self):\n"
        "        LEDGER.register_pool('o', lambda: 0)\n"
    )
    got = _fake(pool_bad, ["resource-lifecycle"])
    assert len(got) == 1 and "deregister_pool" in got[0].message
    pool_good = pool_bad + (
        "    def close(self):\n"
        "        LEDGER.deregister_pool('o')\n")
    assert _fake(pool_good, ["resource-lifecycle"]) == []


# ---------------------------------------------------------------------------
# the tier-1 gate: whole-tree run, budget, JSON, exit codes
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tree_findings():
    """The whole tree's findings and the lint's own CPU seconds: the
    process's CPU clock, which the other workers of a parallel test run
    do not inflate as they do the wall clock (the lint runs in this one
    thread, on no pool)."""
    t0 = time.process_time()
    findings = A.run_paths([PKG])
    elapsed = time.process_time() - t0
    return findings, elapsed


def test_full_tree_zero_unsuppressed_under_budget(tree_findings):
    findings, elapsed = tree_findings
    bad = A.unsuppressed(findings)
    assert not bad, "unsuppressed findings:\n  " + "\n  ".join(
        f"{f.where()}: [{f.rule}] {f.message}" for f in bad)
    # every suppression that exists is justified (non-empty reason)
    for f in findings:
        if f.suppressed:
            assert f.suppress_reason.strip()
    # budget raised 10s -> 15s in PR 17: the tree grew to 126+ files
    # (typical run ~4-5s, vs 2.4s when PR 13 set 10s) and single-core
    # CI boxes spike 2x under load.  It holds the lint's CPU time, not
    # its wall time: under six test workers the wall read 15.6 s for
    # ~12.8 s of work, a budget of the host's load; the CPU budget still
    # catches any super-linear regression of the linter itself
    assert elapsed <= 15.0, \
        f"filolint full-tree run took {elapsed:.1f}s of CPU (budget 15s)"


def test_cli_json_output_for_ci(capsys):
    rc = lint_main([str(PKG), "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out)
    assert doc["summary"]["findings"] == 0
    assert doc["summary"]["files"] >= 100
    assert doc["summary"]["suppressed"] >= 1
    for f in doc["findings"]:
        assert {"rule", "path", "line", "message", "severity",
                "suppressed", "suppress_reason"} <= set(f)


def test_cli_nonzero_on_violation(tmp_path, capsys):
    bad = tmp_path / "wedge.py"
    bad.write_text(
        "import urllib.request\n"
        "class ReplicaFanout:\n"
        "    def publish(self, c):\n"
        "        with self._lock:\n"
        "            urllib.request.urlopen(req, timeout=5)\n")
    assert lint_main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "blocking-under-lock" in out


def test_overlapping_paths_do_not_double_load(capsys):
    """A dir + a file inside it must not load the module twice — the
    duplicate's suppressions would report as falsely stale."""
    target = PKG / "native" / "baseline.py"   # carries a suppression
    rc = lint_main([str(PKG / "native"), str(target), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0, doc["summary"]
    assert doc["summary"]["findings"] == 0


def test_match_statement_bodies_are_walked():
    src = (
        "import time\n"
        "class C:\n"
        "    def f(self, x):\n"
        "        with self._lock:\n"
        "            match x:\n"
        "                case 1:\n"
        "                    time.sleep(5)\n"
    )
    got = _fake(src, ["blocking-under-lock"])
    assert len(got) == 1 and "sleep" in got[0].message


def test_cli_lint_verb_passes_through(capsys):
    from filodb_tpu.cli import main as cli_main
    rc = cli_main(["lint", str(PKG / "analysis"), "--show-suppressed",
                   "--rules", "decode-sentinel"])
    out = capsys.readouterr().out
    assert rc == 0 and "filolint:" in out


def test_cli_list_rules(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in ("lock-discipline", "blocking-under-lock",
                 "resource-lifecycle", "decode-sentinel", "metric-doc"):
        assert name in out


def test_deleting_any_suppression_makes_it_fail(tree_findings):
    """Acceptance: deleting any ONE suppression comment flips the tree
    run nonzero — i.e. every suppression in the tree covers a finding
    that would otherwise fire right there."""
    findings, _ = tree_findings
    suppressed = [f for f in findings if f.suppressed]
    assert suppressed, "expected at least one justified suppression"
    for f in suppressed:
        path = REPO / f.path
        lines = path.read_text().splitlines(keepends=True)
        ln = lines[f.line - 1]
        assert "# filolint:" in ln, (f.path, f.line)
        lines[f.line - 1] = ln[:ln.index("# filolint:")].rstrip() + "\n"
        got = _fake("".join(lines), [f.rule], rel=f.path)
        assert any(g.rule == f.rule and g.line == f.line for g in got), \
            f"stripping the suppression at {f.where()} did not re-fire " \
            f"{f.rule}"


# ---------------------------------------------------------------------------
# ISSUE 10: whole-program analyses (call graph, lock order, device)
# ---------------------------------------------------------------------------

_WEDGE_CALLER = (
    "from filodb_tpu.gateway.lanes import deliver\n"
    "class ReplicaFanout:\n"
    "    def publish(self, container):\n"
    "        with self._lock:\n"
    "            deliver(container)\n"
)
_WEDGE_HELPER = (
    "from filodb_tpu.utils.observability import http_container_push\n"
    "def deliver(container):\n"
    "    http_container_push('http://peer', container, timeout_s=5)\n"
)


def test_cross_module_blocking_requires_whole_program():
    """Acceptance: the PR 12 ReplicaFanout wedge SPLIT ACROSS TWO
    MODULES — a ``with self._lock:`` whose blocking peer POST lives in
    another module — is caught by the whole-program fixpoint and
    provably NOT caught by a same-module-only run (this regression
    pins the improvement over PR 13's per-module analysis)."""
    # same-module-only: each module linted alone is silent — the caller
    # cannot resolve deliver(), the helper holds no lock
    assert _fake(_WEDGE_CALLER, ["blocking-under-lock"],
                 rel="filodb_tpu/gateway/fanout.py") == []
    assert _fake(_WEDGE_HELPER, ["blocking-under-lock"],
                 rel="filodb_tpu/gateway/lanes.py") == []
    # whole-program: the same two sources linted TOGETHER fire at the
    # lock-taking caller, with the cross-module chain in the message
    got = A.unsuppressed(A.run_sources(
        {"filodb_tpu/gateway/fanout.py": _WEDGE_CALLER,
         "filodb_tpu/gateway/lanes.py": _WEDGE_HELPER},
        rules=["blocking-under-lock"]))
    assert len(got) == 1
    f = got[0]
    assert f.path == "filodb_tpu/gateway/fanout.py" and f.line == 5
    assert "http_container_push" in f.message
    assert "via lanes.deliver" in f.message


def test_self_attr_call_resolves_through_init_class():
    """``self.x.m()`` where __init__ assigned x a known class resolves
    cross-module (best-effort attribute typing)."""
    caller = (
        "from filodb_tpu.coordinator.lanes import PeerLane\n"
        "class Fanout:\n"
        "    def __init__(self):\n"
        "        self._lane = PeerLane()\n"
        "    def publish(self, c):\n"
        "        with self._lock:\n"
        "            self._lane.deliver(c)\n"
    )
    helper = (
        "import time\n"
        "class PeerLane:\n"
        "    def deliver(self, c):\n"
        "        time.sleep(1)\n"
    )
    got = A.unsuppressed(A.run_sources(
        {"filodb_tpu/coordinator/fanout.py": caller,
         "filodb_tpu/coordinator/lanes.py": helper},
        rules=["blocking-under-lock"]))
    assert len(got) == 1 and got[0].line == 7
    assert "sleep" in got[0].message


def test_cross_module_lock_order_cycle():
    moda = (
        "import threading\n"
        "from filodb_tpu.memstore.other import grab_b\n"
        "_A_LOCK = threading.Lock()\n"
        "def fwd():\n"
        "    with _A_LOCK:\n"
        "        grab_b()\n"
        "def take_a():\n"
        "    with _A_LOCK:\n"
        "        pass\n"
    )
    modb = (
        "import threading\n"
        "from filodb_tpu.memstore.faker import take_a\n"
        "_B_LOCK = threading.Lock()\n"
        "def grab_b():\n"
        "    with _B_LOCK:\n"
        "        pass\n"
        "def rev():\n"
        "    with _B_LOCK:\n"
        "        take_a()\n"
    )
    got = A.unsuppressed(A.run_sources(
        {"filodb_tpu/memstore/faker.py": moda,
         "filodb_tpu/memstore/other.py": modb},
        rules=["lock-order-cycle"]))
    assert len(got) == 1
    assert "_A_LOCK" in got[0].message and "_B_LOCK" in got[0].message
    # each module alone sees only its own half — no cycle
    assert _fake(moda, ["lock-order-cycle"],
                 rel="filodb_tpu/memstore/faker.py") == []
    assert _fake(modb, ["lock-order-cycle"],
                 rel="filodb_tpu/memstore/other.py") == []


def test_lock_order_proactive_declaration_binds_to_acquired_locks():
    """A declaration over two locks that are each acquired but never
    yet nested (the advertised proactive workflow) must NOT read as
    binding to nothing."""
    src = (
        "class A:\n"
        "    def f(self):\n"
        "        # lock-order: _a_lock < _b_lock\n"
        "        with self._a_lock:\n"
        "            pass\n"
        "class B:\n"
        "    def g(self):\n"
        "        with self._b_lock:\n"
        "            pass\n"
    )
    assert _fake(src, ["lock-order-inversion"]) == []


def test_host_sync_ok_in_docstring_is_not_an_annotation():
    """A docstring QUOTING the annotation syntax is neither a live
    annotation nor a stale one (comment-token discipline, same as the
    engine's suppression scanner)."""
    src = (
        '"""Declare readbacks with ``# host-sync-ok: <reason>``."""\n'
        "x = 1\n"
    )
    assert _fake(src, ["host-sync-annotation"],
                 rel="filodb_tpu/query/fake.py") == []


def test_same_named_plain_function_is_not_a_jit_entry():
    """A nested jit closure must not hijack name resolution for an
    unrelated same-named module-level function."""
    src = (
        "from filodb_tpu.utils import devicewatch\n"
        "def factory():\n"
        "    @devicewatch.jit\n"
        "    def kernel(a):\n"
        "        return a\n"
        "    return kernel\n"
        "def kernel(rows, cols):\n"
        "    return rows * cols\n"
        "def serve(xs):\n"
        "    return kernel(len(xs), 4)\n"
    )
    assert _fake(src, ["recompile-hazard"]) == []


def test_lock_order_dangling_declaration_is_an_error():
    src = (
        "class C:\n"
        "    def __init__(self):\n"
        "        # lock-order: _no_such_lock < _lock\n"
        "        self._lock = mk()\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            pass\n"
    )
    got = _fake(src, ["lock-order-inversion"])
    assert any("binds to nothing" in f.message for f in got)


def test_lock_order_declaration_pins_real_partition_edge():
    """Flipping the in-tree declared encode->buffer order must fire
    against the REAL acquisition edge in partition.py."""
    src = (REPO / "filodb_tpu/memstore/partition.py").read_text()
    decl = "# lock-order: _encode_lock < TimeSeriesPartition._lock"
    assert decl in src
    flipped = src.replace(
        decl, "# lock-order: TimeSeriesPartition._lock < _encode_lock")
    got = _fake(flipped, ["lock-order-inversion"],
                rel="filodb_tpu/memstore/partition.py")
    assert any("_encode_lock" in f.message for f in got)
    assert _fake(src, ["lock-order-inversion"],
                 rel="filodb_tpu/memstore/partition.py") == []


def test_stripping_any_host_sync_ok_refires():
    """Every # host-sync-ok annotation this PR seeded covers a live
    host-sync finding — stripping any one re-fires it (the delete-any-
    suppression sweep, extended to the device allowlist)."""
    total = 0
    for rel in ("filodb_tpu/memstore/devicestore.py",
                "filodb_tpu/parallel/mesh.py",
                "filodb_tpu/parallel/meshgrid.py"):
        src = (REPO / rel).read_text()
        lines = src.splitlines(keepends=True)
        marks = [i for i, ln in enumerate(lines) if "# host-sync-ok:" in ln]
        assert marks, f"{rel}: expected seeded annotations"
        total += len(marks)
        for i in marks:
            stripped = lines[:]
            stripped[i] = stripped[i][
                :stripped[i].index("# host-sync-ok:")].rstrip() + "\n"
            got = _fake("".join(stripped), ["host-sync"], rel=rel)
            assert any(g.line == i + 1 for g in got), \
                f"stripping {rel}:{i + 1} did not re-fire host-sync"
        # and the file as-is is clean (annotations used, none stale)
        assert _fake(src, ["host-sync", "host-sync-annotation"],
                     rel=rel) == []
    assert total >= 19


def test_recompile_hazard_via_local_fstring_binding():
    src = (
        "from filodb_tpu.utils import devicewatch\n"
        "@devicewatch.jit\n"
        "def prog(x, tag):\n"
        "    return x\n"
        "def serve(xs, x):\n"
        "    for i, _ in enumerate(xs):\n"
        "        key = f'k{i}'\n"
        "        prog(x, key)\n"
    )
    got = _fake(src, ["recompile-hazard"])
    assert len(got) == 1 and "f-string" in got[0].message


def test_vmem_budget_knob_and_scratch(tmp_path, capsys):
    from filodb_tpu.analysis import device as D
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "def kern(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...]\n"
        "def f(x):\n"
        "    return pl.pallas_call(\n"
        "        kern,\n"
        "        out_shape=jax.ShapeDtypeStruct((256, 1024), jnp.float32),\n"
        "        in_specs=[pl.BlockSpec((256, 1024), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((256, 1024), lambda i: (i, 0)),\n"
        "    )(x)\n"
    )
    # 2 MiB of blocks: clean at the 16 MiB default, over a 1 MiB budget
    assert _fake(src, ["vmem-budget"]) == []
    p = tmp_path / "k.py"
    p.write_text(src)
    try:
        assert lint_main([str(p), "--vmem-budget-mib", "1"]) == 1
        out = capsys.readouterr().out
        assert "vmem-budget" in out
    finally:
        D.VMEM_BUDGET_BYTES = D.DEFAULT_VMEM_BUDGET_BYTES
    assert lint_main([str(p)]) == 0
    capsys.readouterr()


def test_unresolvable_dims_do_not_fire():
    """Variable BlockSpec dims (the real grid.py shape) are skipped —
    the rule under-counts rather than guessing."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "def f(x, nb, lanes, kern):\n"
        "    return pl.pallas_call(\n"
        "        kern,\n"
        "        out_shape=jax.ShapeDtypeStruct((nb, lanes), jnp.float32),\n"
        "        in_specs=[pl.BlockSpec((nb, lanes), lambda i: (0, i))],\n"
        "        out_specs=pl.BlockSpec((nb, lanes), lambda i: (0, i)),\n"
        "    )(x)\n"
    )
    assert _fake(src, ["vmem-budget"]) == []


# ---------------------------------------------------------------------------
# ISSUE 10 satellites: --changed, --format=github, exit codes
# ---------------------------------------------------------------------------


def test_exit_code_2_on_usage_errors(capsys):
    assert lint_main(["--rules", "no-such-rule"]) == 2
    assert lint_main([str(PKG / "analysis"),
                      "--changed", "not-a-real-ref"]) == 2
    capsys.readouterr()


def test_format_github_annotations(tmp_path, capsys):
    bad = tmp_path / "wedge.py"
    bad.write_text(
        "import urllib.request\n"
        "class ReplicaFanout:\n"
        "    def publish(self, c):\n"
        "        with self._lock:\n"
        "            urllib.request.urlopen(req, timeout=5)\n")
    assert lint_main([str(bad), "--format=github"]) == 1
    out = capsys.readouterr().out
    assert "::error file=wedge.py,line=5,title=filolint" \
           "[blocking-under-lock]::" in out
    assert "::notice::filolint: " in out


def test_changed_subset_scopes_report(capsys):
    """--changed reports ONLY findings in changed files while the
    analysis still runs whole-program; an untracked violation file is
    picked up, and nothing else (incl. stale-suppression verdicts for
    unchanged files) leaks into the report."""
    probe = PKG / "_filolint_changed_probe.py"
    probe.write_text(
        "import urllib.request\n"
        "class ReplicaFanout:\n"
        "    def publish(self, c):\n"
        "        with self._lock:\n"
        "            urllib.request.urlopen(req, timeout=5)\n")
    try:
        rc = lint_main(["--changed", "HEAD", "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1
        open_findings = [f for f in doc["findings"]
                         if not f["suppressed"]]
        assert open_findings, "probe violation not reported"
        probe_rel = "filodb_tpu/_filolint_changed_probe.py"
        assert {f["path"] for f in open_findings} <= {probe_rel}
    finally:
        probe.unlink()
    # with the probe gone the changed-subset run is clean again
    rc = lint_main(["--changed", "HEAD"])
    capsys.readouterr()
    assert rc == 0


def test_cli_lint_forwards_changed_and_format(capsys):
    """cli.py lint must not hand-mirror flags: the new --changed /
    --format options pass straight through."""
    from filodb_tpu.cli import main as cli_main
    rc = cli_main(["lint", "--changed", "HEAD", "--format", "github"])
    out = capsys.readouterr().out
    assert rc == 0 and "::notice::filolint:" in out


def test_reintroducing_fixed_races_fails_the_build():
    """Acceptance: the exact bug shapes this PR fixed fail the build if
    they come back."""
    # 1. StatusPoller.stop clearing _change_pending off _hook_lock
    src = (REPO / "filodb_tpu/coordinator/cluster.py").read_text()
    locked = ("        with self._hook_lock:\n"
              "            self._change_pending.clear()\n")
    assert locked in src
    regressed = src.replace(
        locked, "        self._change_pending.clear()\n")
    got = _fake(regressed, ["lock-discipline"],
                rel="filodb_tpu/coordinator/cluster.py")
    assert any("_change_pending" in g.message for g in got)
    assert _fake(src, ["lock-discipline"],
                 rel="filodb_tpu/coordinator/cluster.py") == []

    # 2. the ODP page-cache pool losing its deregistration path
    src = (REPO / "filodb_tpu/memstore/odp.py").read_text()
    dereg = "LEDGER.deregister_pool(self._ledger_owner)"
    assert dereg in src
    regressed = src.replace(dereg, "pass")
    got = _fake(regressed, ["resource-lifecycle"],
                rel="filodb_tpu/memstore/odp.py")
    assert any("deregister_pool" in g.message for g in got)

    # 3. _SqliteBase.shutdown resetting DDL state off _ddl_lock
    src = (REPO / "filodb_tpu/store/persistence.py").read_text()
    assert "self._ddl_done = False  # guarded-by: _ddl_lock" in src
    regressed = src.replace(
        "        with self._ddl_lock:\n"
        "            mem = getattr(self, \"_mem_conn\", None)",
        "        if True:\n"
        "            mem = getattr(self, \"_mem_conn\", None)")
    got = _fake(regressed, ["lock-discipline"],
                rel="filodb_tpu/store/persistence.py")
    assert any("_ddl_done" in g.message for g in got)
