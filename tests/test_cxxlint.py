"""Static-analyzer tests (tools/cxxlint.py + utils/lockrank.py).

Each rule id gets a minimal fixture package that triggers it EXACTLY
once, and each rule family gets a clean fixture asserting no false
positive — the analyzer is itself review-critical code, and a silent
false negative (rule stops firing) or a noisy false positive (every PR
fights the linter) are both regressions. Plus: the baseline ratchet
semantics (shrink ok / grow fails / stale entry fails), the runtime
lock-rank inversion diagnostic, and the real-package gates (clean tree,
RANKS is a valid topological order of the extracted lock graph).

Everything here is jax-free and cheap: fixtures are tiny synthetic
packages in tmp_path; the one full-package lint run is shared across the
real-tree assertions (tier-1 runs near its 870s budget).
"""

import json
import os
import sys
import threading

import pytest

from cxxnet_tpu.utils import lockrank

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import cxxlint  # noqa: E402


# ----------------------------------------------------------------------
# fixture plumbing
def lint_snippet(tmp_path, files, docs=None):
    """Lint a synthetic package: files maps relpath -> source under
    fixpkg/, docs maps name.md -> markdown (empty doc dir = conf rules
    off, so unrelated fixtures cannot trip the registry)."""
    pkg = tmp_path / "fixpkg"
    pkg.mkdir(exist_ok=True)
    for rel, src in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src, encoding="utf-8")
    docdir = tmp_path / "doc"
    docdir.mkdir(exist_ok=True)
    for name, text in (docs or {}).items():
        (docdir / name).write_text(text, encoding="utf-8")
    return cxxlint.run_lint(str(tmp_path), "fixpkg", str(docdir))


def rules_of(res):
    return [f.rule for f in res.findings]


def assert_fires_once(res, rule):
    rules = rules_of(res)
    assert rules.count(rule) == 1, \
        "%s fired %d times: %r" % (rule, rules.count(rule),
                                   [f.render(os.sep) for f in res.findings])
    assert rules == [rule], "extra findings rode along: %r" % rules
    f = [x for x in res.findings if x.rule == rule][0]
    assert f.line > 0 and f.path
    assert cxxlint.HINTS[rule]   # every rule ships a fix hint


# ----------------------------------------------------------------------
# family (a): concurrency
def test_lock_blocking_fires(tmp_path):
    res = lint_snippet(tmp_path, {"w.py": (
        "import threading\n"
        "import time\n"
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def slow(self):\n"
        "        with self._lock:\n"
        "            time.sleep(0.1)\n")})
    assert_fires_once(res, "lock-blocking")
    f = res.findings[0]
    assert "time.sleep" in f.msg and "_lock" in f.msg


def test_lock_blocking_through_a_call(tmp_path):
    # the blocking op hides one resolvable call away: the closure over
    # the call graph must still surface it, naming the origin site
    res = lint_snippet(tmp_path, {"w.py": (
        "import threading\n"
        "import time\n"
        "def flush_to_disk(buf):\n"
        "    time.sleep(0.5)\n"
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def slow(self):\n"
        "        with self._lock:\n"
        "            flush_to_disk([])\n")})
    # direct finding inside flush? no lock held there — exactly the
    # call-site finding must fire
    assert rules_of(res) == ["lock-blocking"]
    assert "flush_to_disk" in res.findings[0].msg


def test_lock_cycle_across_two_classes(tmp_path):
    # two independent call paths, opposite orders: A.outer takes
    # la then B's lb; B.rev takes lb then A's la — a 2-cycle neither
    # class can see alone
    res = lint_snippet(tmp_path, {"ab.py": (
        "import threading\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._la = threading.Lock()\n"
        "        self.b = B()\n"
        "    def outer(self):\n"
        "        with self._la:\n"
        "            self.b.poke()\n"
        "    def inner(self):\n"
        "        with self._la:\n"
        "            pass\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        self._lb = threading.Lock()\n"
        "        self.a = A()\n"
        "    def poke(self):\n"
        "        with self._lb:\n"
        "            pass\n"
        "    def rev(self):\n"
        "        with self._lb:\n"
        "            self.a.inner()\n")})
    assert_fires_once(res, "lock-cycle")
    msg = res.findings[0].msg
    assert "_la" in msg and "_lb" in msg


def test_lock_self_cycle_is_a_deadlock(tmp_path):
    res = lint_snippet(tmp_path, {"re.py": (
        "import threading\n"
        "class R:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def get(self):\n"
        "        with self._lock:\n"
        "            return self.peek()\n"
        "    def peek(self):\n"
        "        with self._lock:\n"
        "            return 1\n")})
    assert_fires_once(res, "lock-cycle")


def test_thread_unjoined_fires(tmp_path):
    res = lint_snippet(tmp_path, {"t.py": (
        "import threading\n"
        "def spawn():\n"
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n"
        "    return t\n")})
    assert_fires_once(res, "thread-unjoined")


def test_thread_unjoined_not_fooled_by_suffix_join(tmp_path):
    # regression: the join-detection needs a left word boundary —
    # client.join(",") must not count as joining a thread named t
    res = lint_snippet(tmp_path, {"t.py": (
        "import threading\n"
        "def spawn(client):\n"
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n"
        "    client.join(',')\n"
        "    return t\n")})
    assert rules_of(res) == ["thread-unjoined"]


def test_lock_rank_contradiction_fires(tmp_path):
    # the fixture's own RANKS table inverts the acquisition order the
    # code actually uses — the static rule must catch the drift before
    # the runtime checker starts raising in production
    res = lint_snippet(tmp_path, {
        "utils/lockrank.py": 'RANKS = {"fix.a": 20, "fix.b": 10}\n',
        "m.py": (
            "from .utils import lockrank\n"
            "class M:\n"
            "    def __init__(self):\n"
            '        self._a = lockrank.lock("fix.a")\n'
            '        self._b = lockrank.lock("fix.b")\n'
            "    def both(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n")})
    assert_fires_once(res, "lock-rank")
    assert "fix.a" in res.findings[0].msg \
        and "fix.b" in res.findings[0].msg


def test_concurrency_clean_no_false_positive(tmp_path):
    res = lint_snippet(tmp_path, {"ok.py": (
        "import threading\n"
        "import time\n"
        "class Clean:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._t = threading.Thread(target=print, daemon=True)\n"
        "    def fast(self):\n"
        "        with self._lock:\n"
        "            x = 1 + 1\n"
        "        time.sleep(0.0)  # blocking AFTER release is fine\n"
        "        return x\n"
        "def run():\n"
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n"
        "    t.join()\n")})
    assert rules_of(res) == []


# ----------------------------------------------------------------------
# family (b): jax hazards
def test_wallclock_fires(tmp_path):
    res = lint_snippet(tmp_path, {"c.py": (
        "import time\n"
        "def took():\n"
        "    t0 = time.time()\n"
        "    return t0\n")})
    assert_fires_once(res, "wallclock")


def test_wallclock_suppressed_with_reason(tmp_path):
    res = lint_snippet(tmp_path, {"c.py": (
        "import time\n"
        "def stamp():\n"
        "    # cxxlint: disable=wallclock — epoch for humans, never "
        "subtracted\n"
        "    return time.time()\n")})
    assert rules_of(res) == []
    assert [f.rule for f in res.suppressed] == ["wallclock"]


def test_inline_suppression_does_not_cover_next_line(tmp_path):
    # regression: an inline suppression covers its own line ONLY — a
    # fresh violation added directly under an existing suppression must
    # still surface (it used to be silently absorbed)
    res = lint_snippet(tmp_path, {"c.py": (
        "import time\n"
        "def f():\n"
        "    t0 = time.time()  # cxxlint: disable=wallclock — epoch\n"
        "    t1 = time.time()\n"
        "    return t0, t1\n")})
    assert rules_of(res) == ["wallclock"]
    assert res.findings[0].line == 4
    assert [s.line for s in res.suppressed] == [3]


def test_suppression_without_reason_is_a_finding(tmp_path):
    res = lint_snippet(tmp_path, {"c.py": (
        "import time\n"
        "def stamp():\n"
        "    return time.time()  # cxxlint: disable=wallclock\n")})
    assert rules_of(res) == ["bad-suppression"]


def test_donated_reuse_fires(tmp_path):
    res = lint_snippet(tmp_path, {"d.py": (
        "import jax\n"
        "def step(params, grads):\n"
        "    fn = jax.jit(apply, donate_argnums=0)\n"
        "    out = fn(params, grads)\n"
        "    return params\n")})
    assert_fires_once(res, "donated-reuse")
    assert "params" in res.findings[0].msg


def test_traced_branch_fires(tmp_path):
    res = lint_snippet(tmp_path, {"j.py": (
        "import jax\n"
        "@jax.jit\n"
        "def absval(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n")})
    assert_fires_once(res, "traced-branch")
    assert "absval" in res.findings[0].msg


def test_timed_dispatch_fires(tmp_path):
    res = lint_snippet(tmp_path, {"s.py": (
        "import jax\n"
        "from .utils import telemetry\n"
        "def bench(xs):\n"
        "    fn = jax.jit(compute)\n"
        '    with telemetry.span("bench.step"):\n'
        "        out = fn(xs)\n"
        "    return out\n")})
    assert_fires_once(res, "timed-dispatch")


def test_jax_clean_no_false_positive(tmp_path):
    res = lint_snippet(tmp_path, {"ok.py": (
        "import time\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from .utils import telemetry\n"
        "def step(params, grads):\n"
        "    fn = jax.jit(apply, donate_argnums=0)\n"
        "    params = fn(params, grads)   # rebound: donation is safe\n"
        "    return params\n"
        "@jax.jit\n"
        "def absval(x):\n"
        "    return jnp.where(x > 0, x, -x)\n"
        "def bench(xs):\n"
        "    fn = jax.jit(compute)\n"
        "    t0 = time.monotonic()\n"
        '    with telemetry.span("bench.step"):\n'
        "        out = jax.block_until_ready(fn(xs))\n"
        "    return out, time.monotonic() - t0\n")})
    assert rules_of(res) == []


# ----------------------------------------------------------------------
# family (c): conf-key registry
CONF_READER = (
    "class Net:\n"
    "    def set_param(self, name, val):\n"
    '        if name == "alpha":\n'
    "            self.alpha = float(val)\n"
    '        if name == "beta":\n'
    "            self.beta = float(val)\n")

CONF_DOC = ("# keys\n\n"
            "| key | meaning |\n"
            "|---|---|\n"
            "| `alpha` | step size |\n")


def test_conf_undocumented_fires(tmp_path):
    res = lint_snippet(tmp_path, {"n.py": CONF_READER},
                       docs={"global.md": CONF_DOC})
    assert_fires_once(res, "conf-undocumented")
    assert "beta" in res.findings[0].msg


def test_conf_dead_fires(tmp_path):
    res = lint_snippet(
        tmp_path, {"n.py": CONF_READER},
        docs={"global.md": CONF_DOC + "| `beta` | momentum |\n"
                                      "| `gamma` | unused relic |\n"})
    assert_fires_once(res, "conf-dead")
    assert "gamma" in res.findings[0].msg


def test_conf_clean_no_false_positive(tmp_path):
    res = lint_snippet(
        tmp_path, {"n.py": CONF_READER},
        docs={"global.md": CONF_DOC + "| `beta` | momentum |\n"})
    assert rules_of(res) == []


# ----------------------------------------------------------------------
# family (d): metric registry
def test_metric_name_fires(tmp_path):
    res = lint_snippet(tmp_path, {"m.py": (
        "from .utils import telemetry\n"
        "def f():\n"
        '    telemetry.count("serve requests!")\n')})
    assert_fires_once(res, "metric-name")


def test_metric_type_fires(tmp_path):
    res = lint_snippet(tmp_path, {"m.py": (
        "from .utils import telemetry\n"
        "def f():\n"
        '    telemetry.count("serve.load")\n'
        '    telemetry.gauge("serve.load")\n')})
    assert_fires_once(res, "metric-type")


def test_metric_suffix_fires(tmp_path):
    # statusd appends _total to counters at scrape time: a raw name
    # already carrying it would render serve_requests_total_total
    res = lint_snippet(tmp_path, {"m.py": (
        "from .utils import telemetry\n"
        "def f():\n"
        '    telemetry.count("serve.requests_total")\n')})
    assert_fires_once(res, "metric-suffix")


def test_metric_collision_fires(tmp_path):
    res = lint_snippet(tmp_path, {"m.py": (
        "from .utils import telemetry\n"
        "def f():\n"
        '    telemetry.count("serve.reqs")\n'
        '    telemetry.count("serve/reqs")\n')})
    assert_fires_once(res, "metric-collision")


def test_metric_clean_no_false_positive(tmp_path):
    res = lint_snippet(tmp_path, {"m.py": (
        "from .utils import telemetry\n"
        "def f(dt):\n"
        '    telemetry.count("serve.requests")\n'
        '    telemetry.gauge("serve.queue_depth", 3)\n'
        '    telemetry.hist("serve.request", dt)\n')})
    assert rules_of(res) == []


def test_metric_doc_fires_on_undocumented_series(tmp_path):
    # an exported series missing from the doc metric tables — named by
    # its SCRAPE name (sanitized + type suffix), what an operator greps
    res = lint_snippet(tmp_path, {"m.py": (
        "from .utils import telemetry\n"
        "def f():\n"
        '    telemetry.count("serve.requests")\n')},
        docs={"observability.md": "no metric tables here\n"})
    assert_fires_once(res, "metric-doc")
    assert "cxxnet_serve_requests_total" in res.findings[0].msg


def test_metric_doc_latch_without_clear_fires(tmp_path):
    # a transition-latch event with a set site but no constant clear
    # site: the timeline would open episodes that never end
    res = lint_snippet(tmp_path, {
        "autopsy.py":
            'TRANSITION_EVENTS = {"kv_pressure": "pressure"}\n',
        "m.py": (
            "from .utils import telemetry\n"
            "def f():\n"
            '    telemetry.event({"ev": "kv_pressure",'
            ' "pressure": 1})\n')},
        docs={"observability.md": "x\n"})
    assert_fires_once(res, "metric-doc")
    assert "kv_pressure" in res.findings[0].msg


def test_metric_doc_clean_no_false_positive(tmp_path):
    res = lint_snippet(tmp_path, {
        "autopsy.py":
            'TRANSITION_EVENTS = {"kv_pressure": "pressure"}\n',
        "m.py": (
            "from .utils import telemetry\n"
            "def f():\n"
            '    telemetry.count("serve.requests")\n'
            '    telemetry.event({"ev": "kv_pressure",'
            ' "pressure": 1})\n'
            '    telemetry.event({"ev": "kv_pressure",'
            ' "pressure": 0})\n')},
        docs={"observability.md":
              "| `cxxnet_serve_requests_total` | door count |\n"})
    assert rules_of(res) == []


def test_metric_doc_off_without_doc_files(tmp_path):
    # neither observability.md nor serving.md in the doc dir: the rule
    # is OFF (synthetic fixture packages must not drown in findings),
    # exactly like the conf registry with no global.md
    res = lint_snippet(tmp_path, {"m.py": (
        "from .utils import telemetry\n"
        "def f():\n"
        '    telemetry.count("serve.requests")\n')})
    assert rules_of(res) == []


# ----------------------------------------------------------------------
# baseline ratchet
def fp(rule, n):
    return cxxlint.Finding(rule, os.path.join(REPO, "x.py"), n,
                           "seeded", key="k%d" % n)


def test_ratchet_clean_baseline_passes():
    new, grand, stale = cxxlint.ratchet([], REPO, {})
    assert (new, grand, stale) == ([], [], [])


def test_ratchet_grandfathers_exactly_the_baseline():
    f1 = fp("wallclock", 1)
    base = {f1.fingerprint(REPO): 1}
    new, grand, stale = cxxlint.ratchet([f1], REPO, base)
    assert new == [] and grand == [f1] and stale == []


def test_ratchet_growth_fails():
    f1, f2 = fp("wallclock", 1), fp("wallclock", 2)
    base = {f1.fingerprint(REPO): 1}
    new, grand, stale = cxxlint.ratchet([f1, f2], REPO, base)
    assert new == [f2] and grand == [f1] and stale == []


def test_ratchet_stale_entry_fails():
    # the violation was fixed but the baseline still grandfathers it:
    # the debt entry must shrink with the debt, or the ratchet is soft
    f1 = fp("wallclock", 1)
    base = {f1.fingerprint(REPO): 1, "wallclock|gone.py|k9": 1}
    new, grand, stale = cxxlint.ratchet([f1], REPO, base)
    assert new == [] and stale == ["wallclock|gone.py|k9"]


def test_ratchet_count_shrink_is_stale_too():
    f1 = fp("wallclock", 1)
    base = {f1.fingerprint(REPO): 1}
    base[f1.fingerprint(REPO)] = 2     # baseline says two, tree has one
    new, grand, stale = cxxlint.ratchet([f1], REPO, base)
    assert new == [] and stale == [f1.fingerprint(REPO)]


def test_update_baseline_round_trips(tmp_path, monkeypatch):
    # --update-baseline writes what ratchet() then accepts verbatim
    findings = [fp("wallclock", 1), fp("wallclock", 1)]
    counts = cxxlint.counts_of(findings, REPO)
    path = tmp_path / "base.json"
    path.write_text(json.dumps(counts), encoding="utf-8")
    loaded = cxxlint.load_baseline(str(path))
    new, grand, stale = cxxlint.ratchet(findings, REPO, loaded)
    assert new == [] and stale == [] and len(grand) == 2


# ----------------------------------------------------------------------
# runtime lock-rank enforcement
def test_lockrank_inversion_names_both_locks_and_sites(monkeypatch):
    monkeypatch.setenv("CXXNET_LOCKRANK", "1")
    outer = lockrank.lock("servd.queue")        # rank 10
    inner = lockrank.lock("telemetry.registry")  # rank 100
    with outer:
        with inner:
            pass                                 # in order: silent
    assert lockrank.held() == []
    with pytest.raises(lockrank.LockOrderError) as ei:
        with inner:
            with outer:                          # inversion
                pass
    msg = str(ei.value)
    assert "servd.queue" in msg and "telemetry.registry" in msg
    assert msg.count(".py:") >= 2, \
        "diagnostic must carry both acquisition sites: " + msg
    assert lockrank.held() == [], "stack leaked after the raise"
    # a condition-entered inversion reports THIS file as the site, not
    # the threading.py internals the acquisition passes through
    cond = lockrank.condition("servd.conn")      # rank 30
    with pytest.raises(lockrank.LockOrderError) as ei2:
        with inner:                              # rank 100
            with cond:
                pass
    assert "threading.py" not in str(ei2.value), str(ei2.value)
    assert "test_cxxlint.py" in str(ei2.value)
    assert lockrank.held() == []


def test_lockrank_off_is_silent_and_late_enable_enforces(monkeypatch):
    monkeypatch.delenv("CXXNET_LOCKRANK", raising=False)
    # enforcement is gated per ACQUISITION, not at construction
    a, b = lockrank.lock("telemetry.registry"), lockrank.lock("servd.queue")
    with a:
        with b:
            pass             # inverted order silent when off
    assert lockrank.held() == []
    # the SAME objects enforce once the env flips on — import-time
    # singletons (the module-level telemetry registry) must not escape
    monkeypatch.setenv("CXXNET_LOCKRANK", "1")
    with pytest.raises(lockrank.LockOrderError):
        with a:
            with b:
                pass
    assert lockrank.held() == []


def test_module_level_telemetry_registry_lock_is_enforced(monkeypatch):
    # the innermost lock of the whole rank table is built at telemetry
    # import time, long before any test or selftest can flip the env —
    # it must still participate in enforcement
    from cxxnet_tpu.utils import telemetry
    assert isinstance(telemetry._REG._lock, lockrank.RankedLock)
    monkeypatch.setenv("CXXNET_LOCKRANK", "1")
    with pytest.raises(lockrank.LockOrderError):
        with telemetry._REG._lock:
            with lockrank.lock("servd.queue"):   # 100 -> 10: inversion
                pass
    assert lockrank.held() == []


def test_lockrank_condition_wait_keeps_stack_honest(monkeypatch):
    monkeypatch.setenv("CXXNET_LOCKRANK", "1")
    cond = lockrank.condition("servd.conn")      # rank 30
    inner = lockrank.lock("servd.stats")         # rank 50
    done = []

    def waiter():
        with cond:
            while not done:
                cond.wait(1.0)
            with inner:                          # re-take kept rank 30
                done.append("ok")

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    import time
    time.sleep(0.05)
    with cond:
        done.append("go")
        cond.notify()
    t.join(2.0)
    assert "ok" in done
    # regression: every wait() used to leak a phantom held-lock entry
    # on the waiting thread (Condition.__init__ binds acquire/release
    # from the inner lock as instance attributes, shadowing subclass
    # overrides) — a later in-order acquisition then raised a bogus
    # self-inversion
    with cond:
        cond.wait(0.01)          # timed-out wait on THIS thread
    assert lockrank.held() == [], \
        "condition wait leaked: %r" % lockrank.held()
    with lockrank.lock("servd.queue"):   # would raise on the leak
        pass


# ----------------------------------------------------------------------
# the real package
@pytest.fixture(scope="module")
def real_lint():
    return cxxlint.run_lint()


def test_real_tree_parses_and_is_clean(real_lint):
    assert real_lint.project.parse_errors == []
    assert len(real_lint.project.modules) > 10
    baseline = cxxlint.load_baseline(cxxlint.BASELINE)
    new, _, stale = cxxlint.ratchet(real_lint.findings, cxxlint.ROOT,
                                    baseline)
    assert new == [], "\n".join(f.render(cxxlint.ROOT) for f in new)
    assert stale == [], "stale baseline entries: %r" % stale


def test_real_suppressions_all_carry_reasons(real_lint):
    # every shipped suppression documents why (bad-suppression covers
    # the mechanics; this asserts the tree actually uses it)
    assert real_lint.suppressed, "expected shipped suppressions"
    for mod in real_lint.project.modules.values():
        for line, (rules, reason) in mod.suppress.items():
            if cxxlint.SUPPRESS_RE.search(mod.lines[line - 1] or ""):
                assert reason, "%s:%d suppression has no reason" \
                    % (mod.path, line)


def test_ranks_are_a_topological_order_of_the_real_graph(real_lint):
    # the runtime table and the static graph must agree, or lockrank
    # raises on orderings the analyzer proved safe (and vice versa)
    edges = real_lint.edges
    assert edges, "lock graph came out empty — resolution broke"
    for (a, b) in edges:
        ra = lockrank.RANKS.get(a)
        rb = lockrank.RANKS.get(b)
        if ra is not None and rb is not None:
            assert ra < rb, \
                "edge %s -> %s contradicts RANKS (%d >= %d)" \
                % (a, b, ra, rb)
    # and the graph the doc tells people to inspect is printable
    order = cxxlint.topo_ranks(edges)
    assert set(order) == {n for e in edges for n in e}


# ----------------------------------------------------------------------
# err-vocab: every ERR string servd/routerd can emit must be a row of
# serving.md's error-vocabulary table (the wire contract the fleet
# router dispatches retry/replay/relay on)

ERR_DOC = (
    "# serving\n\n### Error vocabulary\n\n"
    "| error line | meaning |\n|---|---|\n"
    "| `ERR busy queue full (N)` | shed |\n"
    "| `ERR busy tenant <t> over fair share ...` | fair-share shed |\n"
    "| `ERR backend ...` | backend raised |\n\n"
    "## next section\n\n`ERR bogus thing` outside the table does "
    "not count.\n")


def test_err_vocab_fires_on_undocumented_error_string(tmp_path):
    res = lint_snippet(tmp_path, {"servd.py": (
        'MSG = "ERR wedged backend stuck"\n')},
        docs={"serving.md": ERR_DOC})
    assert_fires_once(res, "err-vocab")


def test_err_vocab_matching_rules(tmp_path):
    # %-format tokens, placeholder/`(N)` doc tokens, `...` tails and
    # code-side prefixes ("ERR backend " + detail) all match; the rule
    # only watches the wire-speaking modules, and a span outside the
    # vocabulary section does not whitelist anything
    res = lint_snippet(tmp_path, {
        "servd.py": (
            'A = "ERR busy queue full (%d)" % 4\n'
            'B = "ERR busy tenant %s over fair share (evicted)"\n'
            'C = "ERR backend " + "boom"\n'
            'D = "ERR %s %s"\n'),
        "other.py": 'E = "ERR wedged not a wire module"\n'},
        docs={"serving.md": ERR_DOC})
    assert "err-vocab" not in rules_of(res)
    res = lint_snippet(tmp_path, {"routerd.py": (
        'F = "ERR bogus thing"\n')},
        docs={"serving.md": ERR_DOC})
    assert_fires_once(res, "err-vocab")


def test_err_vocab_off_without_vocabulary_section(tmp_path):
    # a doc tree without the table (or without serving.md at all)
    # disables the rule instead of flagging every error string
    res = lint_snippet(tmp_path, {"servd.py": (
        'MSG = "ERR wedged backend stuck"\n')},
        docs={"serving.md": "# serving\n\nno table here\n"})
    assert rules_of(res) == []


def test_err_vocab_real_tree_is_clean(real_lint):
    # the shipped servd/routerd error strings are all documented —
    # the baseline carries ZERO err-vocab debt
    assert [f for f in real_lint.findings
            if f.rule == "err-vocab"] == []
