"""repro.analysis tests (DESIGN.md §12): every lint rule catches its seeded
violation and stays quiet on the fixed shape; suppression (inline allows,
baseline) round-trips; the trace auditors (assert_traces / audit_dtypes /
audit_donation) and the dist protocol checks (verb grammar FSM, static verb
audit, ParameterStore lock discipline) each fail on a doctored input and pass
on the real tree. Plus the two retrace gates the subsystem exists to guard:
the ServeEngine decode dispatch and the chunked trainloop dispatch both trace
exactly once across a steady-state run.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    DonationReport,
    TraceCountError,
    apply_baseline,
    assert_traces,
    audit_donation,
    audit_dtypes,
    audit_lock_discipline,
    audit_verbs,
    check_sequence,
    lint_source,
    load_baseline,
    save_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def rules_of(findings, rule):
    return [f for f in findings if f.rule == rule]


def lint(src, path):
    return lint_source(textwrap.dedent(src), path)


# ------------------------------------------------------------ lint rules


class TestHostSyncRule:
    PATH = "src/repro/serve/engine.py"  # hot scopes: ServeEngine.step = "all"

    def test_sync_in_hot_scope_flagged(self):
        src = """
        class ServeEngine:
            def step(self):
                a = float(x)
                b = np.asarray(y)
                c = jax.device_get(z)
                d = w.item()
        """
        found = rules_of(lint(src, self.PATH), "host-sync-in-hot-loop")
        assert len(found) == 4
        assert {f.line for f in found} == {4, 5, 6, 7}

    def test_cold_function_not_flagged(self):
        src = """
        class ServeEngine:
            def stats(self):
                return float(x)  # setup/teardown path, not a hot scope
        """
        assert rules_of(lint(src, self.PATH), "host-sync-in-hot-loop") == []

    def test_loops_mode_only_flags_loop_bodies(self):
        path = "src/repro/engine/trainloop.py"  # fit = "loops"
        src = """
        def fit(spec):
            setup = np.asarray(w)      # one-time staging: fine
            for step in range(n):
                loss = float(m)        # per-chunk sync: flagged
            return np.asarray(loss)    # teardown: fine
        """
        found = rules_of(lint(src, path), "host-sync-in-hot-loop")
        assert [f.line for f in found] == [5]

    def test_inline_allow_suppresses(self):
        src = """
        class ServeEngine:
            def step(self):
                t = jax.device_get(x)  # lint: allow[host-sync-in-hot-loop] the one batched transfer
        """
        assert rules_of(lint(src, self.PATH), "host-sync-in-hot-loop") == []


class TestJitInLoopRule:
    PATH = "src/repro/foo.py"  # not a donate module: isolates the rule

    def test_jit_in_loop_flagged(self):
        src = """
        def run(fns):
            for fn in fns:
                g = jax.jit(fn)
                h = pl.pallas_call(kernel, out_shape=s)
        """
        found = rules_of(lint(src, self.PATH), "jit-in-loop")
        assert {f.line for f in found} == {4, 5}

    def test_hoisted_jit_clean(self):
        src = """
        def run(fn, xs):
            g = jax.jit(fn)
            for x in xs:
                y = g(x)
        """
        assert rules_of(lint(src, self.PATH), "jit-in-loop") == []


class TestTracedMutationRule:
    PATH = "src/repro/foo.py"

    def test_captured_append_in_jit_target_flagged(self):
        src = """
        acc = []

        @jax.jit
        def f(x):
            acc.append(x)
            return x
        """
        found = rules_of(lint(src, self.PATH), "traced-mutation")
        assert [f.line for f in found] == [6]

    def test_attribute_store_on_param_flagged(self):
        src = """
        @jax.jit
        def f(state, x):
            state.counter = x
            return x
        """
        assert len(rules_of(lint(src, self.PATH), "traced-mutation")) == 1

    def test_name_passed_to_jit_counts_as_traced(self):
        src = """
        def body(x):
            log.append(x)
            return x

        step = jax.jit(body)
        """
        assert len(rules_of(lint(src, self.PATH), "traced-mutation")) == 1

    def test_local_mutation_clean(self):
        src = """
        @jax.jit
        def f(x):
            parts = []
            parts.append(x)
            return parts
        """
        assert rules_of(lint(src, self.PATH), "traced-mutation") == []

    def test_untraced_function_clean(self):
        src = """
        def collect(x):
            acc.append(x)
            return x
        """
        assert rules_of(lint(src, self.PATH), "traced-mutation") == []


class TestF32InF64PathRule:
    def test_f32_literal_in_parity_module_flagged(self):
        src = """
        def widen(x):
            return x.astype(jnp.float32)
        """
        found = rules_of(lint(src, "src/repro/engine/delaysim.py"),
                         "f32-in-f64-path")
        assert len(found) == 1

    def test_f32_string_flagged(self):
        src = """
        def make(shape):
            return np.zeros(shape, dtype='float32')
        """
        assert len(rules_of(lint(src, "src/repro/dist/store.py"),
                            "f32-in-f64-path")) == 1

    def test_promote_types_idiom_allowed(self):
        src = """
        def acc_dtype(w):
            return jnp.promote_types(w.dtype, jnp.float32)
        """
        assert rules_of(lint(src, "src/repro/kernels/guided_update/kernel.py"),
                        "f32-in-f64-path") == []

    def test_non_parity_module_clean(self):
        src = """
        def make(shape):
            return np.zeros(shape, np.float32)
        """
        assert rules_of(lint(src, "src/repro/serve/engine.py"),
                        "f32-in-f64-path") == []


class TestMissingDonateRule:
    PATH = "src/repro/engine/trainloop.py"

    def test_jit_without_donate_flagged(self):
        src = """
        def build(step):
            return jax.jit(step)
        """
        assert len(rules_of(lint(src, self.PATH), "missing-donate")) == 1

    def test_jit_with_donate_clean(self):
        src = """
        def build(step):
            return jax.jit(step, donate_argnums=(0, 1))
        """
        assert rules_of(lint(src, self.PATH), "missing-donate") == []

    def test_non_carry_module_clean(self):
        src = """
        def build(step):
            return jax.jit(step)
        """
        assert rules_of(lint(src, "src/repro/foo.py"), "missing-donate") == []


class TestX64UnscopedJnpRule:
    PATH = "src/repro/dist/store.py"

    def test_unscoped_jnp_flagged(self):
        src = """
        def norm(g):
            return jnp.linalg.norm(g)
        """
        found = rules_of(lint(src, self.PATH), "x64-unscoped-jnp")
        assert len(found) >= 1

    def test_scoped_jnp_clean(self):
        src = """
        def norm(g):
            with jax.enable_x64():
                return jnp.linalg.norm(g)
        """
        assert rules_of(lint(src, self.PATH), "x64-unscoped-jnp") == []

    def test_outside_dist_clean(self):
        src = """
        def norm(g):
            return jnp.linalg.norm(g)
        """
        assert rules_of(lint(src, "src/repro/engine/trainloop.py"),
                        "x64-unscoped-jnp") == []


# ---------------------------------------------------------------- baseline


class TestBaseline:
    SRC = """
    class ServeEngine:
        def step(self):
            a = jax.device_get(x)
    """

    def test_round_trip_suppresses(self, tmp_path):
        findings = lint(self.SRC, "src/repro/serve/engine.py")
        assert findings
        p = tmp_path / "analysis-baseline.json"
        save_baseline(str(p), findings)
        entries = load_baseline(str(p))
        assert entries[0]["count"] == 1 and entries[0]["reason"]
        left, stale = apply_baseline(findings, entries)
        assert left == [] and stale == []

    def test_stale_entry_reported(self, tmp_path):
        findings = lint(self.SRC, "src/repro/serve/engine.py")
        p = tmp_path / "analysis-baseline.json"
        save_baseline(str(p), findings)
        entries = load_baseline(str(p))
        left, stale = apply_baseline([], entries)  # the code was fixed
        assert left == [] and len(stale) == 1

    def test_edited_line_breaks_the_match(self, tmp_path):
        findings = lint(self.SRC, "src/repro/serve/engine.py")
        p = tmp_path / "analysis-baseline.json"
        save_baseline(str(p), findings)
        entries = load_baseline(str(p))
        edited = lint(self.SRC.replace("(x)", "(y)"),
                      "src/repro/serve/engine.py")
        left, stale = apply_baseline(edited, entries)
        assert len(left) == 1 and len(stale) == 1

    def test_committed_baseline_matches_tree(self):
        """The repo's own baseline is live: every entry covers a finding that
        still exists (no stale debt) and the reasons are filled in."""
        entries = load_baseline(os.path.join(REPO, "analysis-baseline.json"))
        for e in entries:
            assert "TODO" not in e["reason"], e


def test_cli_clean_on_repo_tree():
    """`python -m repro.analysis src/` (the `make lint` gate) exits 0 on the
    committed tree with the committed baseline."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "src"],
        cwd=REPO, env={**os.environ, "PYTHONPATH": SRC},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_fails_and_names_the_finding(tmp_path):
    bad = tmp_path / "src" / "repro" / "dist" / "hot.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def f(g):\n    return jnp.sum(g)\n")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--no-protocol",
         str(bad)],
        cwd=REPO, env={**os.environ, "PYTHONPATH": SRC},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "x64-unscoped-jnp" in proc.stdout
    assert "hot.py:2:" in proc.stdout  # file:line for jump-to-source


# ------------------------------------------------------------ assert_traces


class TestAssertTraces:
    def test_counts_jitted_cache_growth(self):
        f = jax.jit(lambda x: x * 2)
        with assert_traces(2, f):
            f(jnp.zeros(3))
            f(jnp.zeros(3))   # cache hit: free
            f(jnp.zeros(4))   # new shape: one more trace

    def test_mismatch_raises_with_breakdown(self):
        f = jax.jit(lambda x: x + 1)
        with pytest.raises(TraceCountError, match="expected exactly 1"):
            with assert_traces(1, f):
                f(jnp.zeros(3))
                f(jnp.zeros((2, 2)))

    def test_holder_attr_target_counts_and_restores(self):
        class Holder:
            @staticmethod
            def fwd(x):
                return x * 3

        original = Holder.fwd
        with assert_traces(1, (Holder, "fwd")):
            jax.jit(lambda x: Holder.fwd(x))(jnp.zeros(3))
        assert Holder.fwd is original

    def test_no_targets_rejected(self):
        with pytest.raises(ValueError):
            with assert_traces(1):
                pass


# ------------------------------------------------------------- audit_dtypes


class TestAuditDtypes:
    def test_seeded_demotion_found(self):

        def leaky(x):
            return jnp.sum(x.astype(jnp.float32))

        with jax.enable_x64():
            viol = audit_dtypes(leaky, jnp.zeros(4, jnp.float64))
        assert viol and viol[0].primitive == "convert_element_type"
        assert "float64" in viol[0].in_dtypes

    def test_demotion_inside_scan_found(self):

        def loop(x):
            def body(c, _):
                return c.astype(jnp.float32).astype(jnp.float64), ()
            c, _ = jax.lax.scan(body, x, None, length=3)
            return c

        with jax.enable_x64():
            viol = audit_dtypes(loop, jnp.zeros(2, jnp.float64))
        assert viol and "scan" in viol[0].path

    def test_f64_preserving_fn_clean(self):

        with jax.enable_x64():
            viol = audit_dtypes(lambda x: jnp.sum(x * 2.0),
                                jnp.zeros(4, jnp.float64))
        assert viol == []

    def test_guided_update_refs_preserve_f64(self):
        """The paper's update rules stay float64 end to end — the runtime
        twin of the f32-in-f64-path lint rule."""

        from repro.kernels.guided_update import ref as R

        with jax.enable_x64():
            w = jnp.ones((8, 4), jnp.float64)
            g = jnp.full((8, 4), .5, jnp.float64)
            assert audit_dtypes(R.guided_sgd_update_ref,
                                w, g, w * .9, 1e-2, .5) == []
            assert audit_dtypes(R.guided_adam_update_ref, w, g, w * .9,
                                w * 0, w * 0, 3, 1e-2, .5, .9, .999, 1e-8) == []


# ----------------------------------------------------------- audit_donation


class TestAuditDonation:
    def test_reports_large_non_donated_args(self):
        params = {"w": np.zeros((256, 256), np.float32)}   # 256 KiB
        gstate = (np.zeros((128, 256), np.float32),)       # 128 KiB
        batch = np.zeros((128, 128), np.float32)           #  64 KiB
        reports = audit_donation([params, gstate, batch], donate_argnums=(0, 1),
                                 names=["params", "gstate", "batch"])
        assert [r.name for r in reports] == ["batch"]  # consumed, not carried

    def test_forgotten_donation_names_the_carry(self):
        params = {"w": np.zeros((256, 256), np.float32)}
        reports = audit_donation([params], donate_argnums=())
        assert reports == [DonationReport(argnum=0, name="arg0",
                                          nbytes=256 * 256 * 4)]
        assert "not donated" in reports[0].format()

    def test_small_args_below_threshold_ignored(self):
        assert audit_donation([np.zeros(4, np.float32)]) == []


# ----------------------------------------------------------- verb grammar


LEGAL_REPLAY = ["hello", "welcome", "pull", "work", "push", "applied",
                "pull", "done", "bye"]
LEGAL_LIVE = ["hello", "welcome", "step", "work", "step", "done", "bye"]


class TestCheckSequence:
    def test_legal_replay_and_live(self):
        assert check_sequence(LEGAL_REPLAY, "replay") == []
        assert check_sequence(LEGAL_LIVE, "live") == []

    def test_push_before_pull_illegal(self):
        viol = check_sequence(["hello", "welcome", "push"], "replay",
                              require_closed=False)
        assert len(viol) == 1
        assert viol[0].verb == "push" and viol[0].state == "ready"
        assert "pull" in viol[0].allowed

    def test_unknown_verb_illegal(self):
        viol = check_sequence(["hello", "poke"], "replay",
                              require_closed=False)
        assert viol and viol[0].verb == "poke"

    def test_unclosed_conversation_flagged(self):
        viol = check_sequence(["hello", "welcome", "pull"], "replay")
        assert viol and viol[-1].verb == "<end>"

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError):
            check_sequence([], mode="chaos")


GOOD_WORKER = """
def run(sock):
    sock.send(("hello", 0))
    sock.send(("pull",))
    sock.send(("push", g, v))
    sock.send(("step", g, v, rows))
    sock.send(("bye",))
"""
GOOD_CHIEF = """
def serve(conn):
    verb = conn.recv()[0]
    if verb == "hello":
        conn.send(("welcome", cfg))
    elif verb == "pull":
        conn.send(("work", t) if t is not None else ("done",))
    elif verb == "push":
        conn.send(("applied", s))
    elif verb == "step":
        conn.send(("work", t) if t is not None else ("done",))
    elif verb == "bye":
        pass
"""


class TestAuditVerbs:
    def test_real_dist_sources_conform(self):
        assert audit_verbs(root=SRC) == []

    def test_fixture_sources_conform(self):
        assert audit_verbs(sources={"worker": GOOD_WORKER,
                                    "chief": GOOD_CHIEF}) == []

    def test_typoed_wire_verb_caught(self):
        doctored = GOOD_WORKER.replace('("pull",)', '("pulll",)')
        msgs = audit_verbs(sources={"worker": doctored, "chief": GOOD_CHIEF})
        assert any("pulll" in m for m in msgs)            # novel verb sent
        assert any("never sends 'pull'" in m for m in msgs)

    def test_unhandled_worker_verb_caught(self):
        deaf = GOOD_CHIEF.replace('elif verb == "push":', 'elif _ == 0:')
        msgs = audit_verbs(sources={"worker": GOOD_WORKER, "chief": deaf})
        assert any("never dispatches on worker verb 'push'" in m for m in msgs)


# -------------------------------------------------------- lock discipline


BAD_STORE = """
class ParameterStore:
    def __init__(self):
        self.cond = threading.Condition()
        self.version = 0
        self.staleness = []

    def push(self, s):
        self.staleness.append(s)    # lock-free container mutation
        self.version += 1

    def locked_push(self, s):
        with self.cond:
            self.staleness.append(s)
            self.version += 1

    def _helper_no_callers(self):
        self.version += 1
"""


class TestLockDiscipline:
    def test_real_store_conforms(self):
        assert audit_lock_discipline(root=SRC) == []

    def test_lock_free_public_mutation_caught(self):
        viol = audit_lock_discipline(source=BAD_STORE)
        by_method = {v.method: v for v in viol}
        assert "push" in by_method
        assert by_method["push"].attr in ("staleness", "version")
        assert "locked_push" not in by_method

    def test_orphan_helper_caught(self):
        viol = audit_lock_discipline(source=BAD_STORE)
        assert any(v.method == "_helper_no_callers" for v in viol)

    def test_helper_with_locked_callers_accepted(self):
        src = BAD_STORE.replace(
            "    def push(self, s):\n"
            "        self.staleness.append(s)    # lock-free container mutation\n"
            "        self.version += 1\n",
            "    def push(self, s):\n"
            "        with self.cond:\n"
            "            self._helper_no_callers()\n")
        viol = audit_lock_discipline(source=src)
        assert viol == []


# ------------------------------------------------------------ retrace gates


def test_serve_decode_traces_once():
    """Steady-state decode is ONE program: a full mixed-length run may grow
    the prefill caches but must trace the decode dispatch exactly once."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.models.module import split_params
    from repro.serve import Request, ServeEngine

    cfg = get_config("minicpm-2b").reduced()
    params = split_params(T.model_init(jax.random.PRNGKey(0), cfg))[0]
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, cfg.vocab_size, (L,)).tolist(),
                    max_new_tokens=4, request_id=i)
            for i, L in enumerate([5, 9, 12, 7])]
    engine = ServeEngine(params, cfg, max_batch=2, max_len=32)
    with assert_traces(1, engine._step):
        engine.run(reqs)


def test_chunked_dispatch_traces_once_per_shape():
    """Same-shape chunk blocks reuse one compiled program; only a new chunk
    size (the uneven tail) may add a trace."""
    from repro.engine.trainloop import build_chunk_step

    def step_fn(params, gstate, batch):
        loss = jnp.sum((params - batch) ** 2)
        return params - 0.1 * batch, gstate + 1, {"loss": loss}

    dispatch = jax.jit(build_chunk_step(step_fn), donate_argnums=(0, 1))
    params, gstate = jnp.zeros(8), jnp.zeros(())
    with assert_traces(1, dispatch):
        for seed in range(3):  # three same-shape (4, 8) blocks
            block = jnp.full((4, 8), float(seed))
            params, gstate, m = dispatch(params, gstate, block)
    with assert_traces(1, dispatch):  # the (2, 8) tail compiles once more
        params, gstate, m = dispatch(params, gstate, jnp.ones((2, 8)))


# ------------------------------------------------- lockset pass (DESIGN §13)


def locks(src, path="src/repro/x.py"):
    from repro.analysis.locks import analyze_source

    return analyze_source(textwrap.dedent(src), path)


class TestLocksPass:
    def test_unlocked_shared_write_flagged(self):
        findings, models = locks("""
        import threading

        class Buf:
            def __init__(self):
                self.lock = threading.Lock()
                self.items = []
                self.n = 0
                threading.Thread(target=self._work).start()

            def _work(self):
                self.items.append(1)   # worker thread, no lock

            def take(self):
                with self.lock:
                    return self.items.pop()
        """)
        assert [m.name for m in models] == ["Buf"]
        hits = rules_of(findings, "lock-shared-unlocked")
        assert len(hits) == 1
        assert "Buf.items" in hits[0].message and "_work" in hits[0].message

    def test_inconsistent_locks_flagged(self):
        findings, _ = locks("""
        import threading

        class Split:
            def __init__(self):
                self.a = threading.Lock()
                self.b = threading.Lock()
                self.n = 0
                threading.Thread(target=self.grow).start()

            def grow(self):
                with self.a:
                    self.n += 1

            def read(self):
                with self.b:
                    return self.n
        """)
        hits = rules_of(findings, "lock-inconsistent")
        assert len(hits) == 1
        assert "no common member" in hits[0].message

    def test_lock_order_cycle_flagged(self):
        findings, _ = locks("""
        import threading

        class AB:
            def __init__(self):
                self.a = threading.Lock()
                self.b = threading.Lock()
                threading.Thread(target=self.fwd).start()

            def fwd(self):
                with self.a:
                    with self.b:
                        pass

            def rev(self):
                with self.b:
                    with self.a:
                        pass
        """)
        hits = rules_of(findings, "lock-order-cycle")
        assert len(hits) == 1
        assert "AB.a" in hits[0].message and "AB.b" in hits[0].message

    def test_disciplined_class_clean(self):
        findings, models = locks("""
        import threading

        class Clean:
            def __init__(self):
                self.cond = threading.Condition()
                self.n = 0
                threading.Thread(target=self._work).start()

            def _work(self):
                with self.cond:
                    self.n += 1
                    self.cond.notify_all()

            def wait_done(self):
                with self.cond:
                    self.cond.wait_for(lambda: self.n > 0)
                    return self.n
        """)
        assert findings == []
        assert models[0].lock_attrs == {"cond"}

    def test_helper_inherits_callers_lock(self):
        # _bump is only ever called with the lock held: entry-lockset
        # propagation proves the unlocked-looking write safe
        findings, _ = locks("""
        import threading

        class Via:
            def __init__(self):
                self.lock = threading.Lock()
                self.n = 0
                threading.Thread(target=self.work).start()

            def work(self):
                with self.lock:
                    self._bump()

            def _bump(self):
                self.n += 1
        """)
        assert findings == []

    def test_single_threaded_class_ignored(self):
        _, models = locks("""
        class Plain:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
        """)
        assert models == []

    def test_real_tree_is_clean(self):
        from repro.analysis.locks import run_locks

        findings, models = run_locks([SRC])
        assert findings == [], [f.format() for f in findings]
        # the four concurrent classes the repo actually has are discovered
        names = {m.name for m in models}
        assert {"ParameterStore", "Chief", "ChunkPrefetcher",
                "AsyncCheckpointer"} <= names

    def test_cross_class_order_is_acyclic_on_real_tree(self):
        from repro.analysis.locks import find_cycles, lock_order_graph, run_locks

        _, models = run_locks([SRC])
        assert find_cycles(lock_order_graph(models)) == []


class TestLockNotWithRule:
    PATH = "src/repro/data/prefetch.py"

    def test_bare_acquire_release_flagged(self):
        src = """
        class P:
            def step(self):
                self.lock.acquire()
                self.n += 1
                self.lock.release()
        """
        hits = rules_of(lint(src, self.PATH), "lock-not-with")
        assert len(hits) == 2

    def test_with_statement_clean(self):
        src = """
        class P:
            def step(self):
                with self.lock:
                    self.n += 1
        """
        assert rules_of(lint(src, self.PATH), "lock-not-with") == []

    def test_inline_allow(self):
        src = """
        class P:
            def step(self):
                self.lock.acquire()  # lint: allow[lock-not-with] handoff
        """
        assert rules_of(lint(src, self.PATH), "lock-not-with") == []
