"""`ParameterStore` — the chief's versioned parameter state + apply path.

One store owns the authoritative weights W, the optimizer accumulator, and
the guided window state. Every applied push increments `version`; the
staleness of an update is OBSERVED, not scripted:

    staleness = version_at_apply - read_version_of_the_push

and the recorded sequence is what `Report.staleness_hist` summarizes. The
apply path drives the same `DelayCompensator` hooks the scan simulator uses
(sim_score / sim_replay / compensate_grads / sim_kernel_lambda), so all six
registered strategies run unmodified on live delay; the arithmetic mirrors
`repro.engine.delaysim`'s scan body in float64 numpy (the fused-kernel math:
gt = g + lam*g*g*(W - W_fetch), then the plain optimizer rule on gt), which
is why a replay-mode run lands on the scan/train_ps trajectory to round-off.

Two grant disciplines share this apply path:

  * replay — the parity oracle. The chief holds the `DelaySchedule` extracted
    by `core.parameter_server.extract_schedule` (same seed -> same table as
    the scan backend) and sequences pulls/pushes against it: worker w's k-th
    pull blocks until `version >= fetch_version` and is served the weights AS
    OF that version (a small version ring keeps the last max_staleness+1
    copies); its push blocks until `version == arrival_step`. Real processes
    compute every gradient; only the interleaving is pinned, so the observed
    staleness sequence must equal the schedule's column — locked in
    tests/test_dist.py.
  * live — free-running. Pushes apply in arrival order at wall-clock speed;
    `drop_rate` injects dropped updates; late pushes after the step budget
    are counted, not crashed on. A `repro.chaos` plan's holds make each
    planted fault land before the budget drains (`_held`).

Thread safety: one lock/condition serializes applies (the parameter server
is sequential by definition — the asynchrony lives between processes).
Strategy hooks trace tiny (rho, P, k) arrays; they run eagerly under a scoped
`jax.enable_x64` so float64 parity survives the jnp round-trip.
"""
from __future__ import annotations

import threading
from collections import deque

import numpy as np


def _aug(X):
    return np.concatenate([X, np.ones((len(X), 1))], axis=1)


def _loss(W, Xa, y):
    """Literal LogisticRegression.loss on pre-augmented rows (float64)."""
    z = Xa @ W
    z = z - z.max(axis=1, keepdims=True)
    lse = np.log(np.exp(z).sum(axis=1))
    return float(np.mean(lse - z[np.arange(len(y)), y]))


def grad(W, Xa, y):
    """Literal LogisticRegression.grad on pre-augmented rows (float64).
    Shared with repro.dist.worker so chief and workers use one arithmetic."""
    z = Xa @ W
    z = z - z.max(axis=1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(axis=1, keepdims=True)
    p[np.arange(len(y)), y] -= 1.0
    return Xa.T @ p / len(y)


def strategy_needs_fetch(strategy) -> bool:
    """True when the strategy compensates against the fetched weights
    (DC-ASGD Taylor term, Gap-Aware dampening): workers then ship W_fetch
    back with the push so the chief never needs an unbounded version ring."""
    from repro.engine.strategies import DelayCompensator

    return bool(strategy.sim_kernel_lambda()) or (
        type(strategy).compensate_grads is not DelayCompensator.compensate_grads
    )


class ParameterStore:
    """Versioned parameter state + the strategy-driven apply path."""

    def __init__(self, spec, strategy, W0, train, val, total_steps: int,
                 schedule=None, drop_rate: float = 0.0, seed: int = 0,
                 checkpointer=None, ckpt_every: int = 0, policy=None,
                 holds=()):
        self.spec = spec
        self.strategy = strategy
        self.W = np.asarray(W0, np.float64).copy()
        self.r = np.zeros_like(self.W)             # rmsprop/adagrad accumulator
        self.Xa = _aug(np.asarray(train[0], np.float64))
        self.y = np.asarray(train[1])
        self.Xva = _aug(np.asarray(val[0], np.float64))
        self.yv = np.asarray(val[1])
        self.version = 0
        self.total = int(total_steps)
        self.lam = float(strategy.sim_kernel_lambda())
        self.guided = bool(strategy.sim_guided)
        self.need_fetch = strategy_needs_fetch(strategy)
        rho = max(spec.rho, 1)
        self.rho = rho
        self.wscore = np.zeros((rho,), np.float64)
        self.wgrads = np.zeros((rho,) + self.W.shape, np.float64)
        self.prev_avg = np.inf
        # ---- observability
        self.history: list = []          # (version, avg_err) per apply
        self.staleness: list = []        # observed per-apply staleness
        self.drops = 0                   # scenario-dropped pushes
        self.late = 0                    # pushes arriving after the budget
        self.joins = 0
        self.worker_exits = 0
        self.bad_frames = 0              # malformed/unparseable worker frames
        self.resets = 0                  # chaos-injected connection resets
        # ---- resilience (DESIGN.md §14): sentinel screen + rollback policy.
        # The screen/detector own no lock — every call happens under `cond`.
        self.policy = policy
        self.screen = None
        self.detector = None
        if policy is not None and policy.screening:
            from repro.resilience import DivergenceDetector, GradScreen

            self.screen = GradScreen(policy)
            if policy.rollback:
                self.detector = DivergenceDetector(policy.factor)
        self.lr_scale = 1.0              # cut by lr_backoff at every rollback
        self.rollbacks = 0
        self.rollback_log: list = []     # (version, restored_step|None, reason)
        self.diverged = 0                # post-apply divergences detected
        self.fatal: Exception | None = None   # set -> drain workers, launcher raises
        # last committed sane state: the rollback target when no verified
        # on-disk snapshot exists (or the dir predates checksums)
        self._good = (self.W.copy(), self.r.copy())
        self._holds = list(holds)       # ChaosPlan.holds, until landed
        self._seen: set = set()         # wids that have said hello
        self._back: dict = {}           # wid -> version it last reconnected at
        # ---- concurrency
        self.cond = threading.Condition()
        self._drop_rng = np.random.default_rng(seed + 7919)
        self.drop_rate = float(drop_rate)
        # ---- checkpointing (chief-side snapshots)
        self._ckpt = checkpointer
        self._ckpt_every = int(ckpt_every)
        # ---- replay grant state
        self.schedule = schedule
        self._ring: dict = {0: self.W.copy()}      # version -> W (replay only)
        self._dispatch: dict = {}                  # wid -> deque of dispatches
        self._ring_keep = 2
        if schedule is not None:
            if schedule.worker is None:
                raise ValueError(
                    "replay mode needs a DelaySchedule with per-arrival worker "
                    "ids (re-extract with the current core.parameter_server)")
            self._ring_keep = int(schedule.max_staleness) + 2
            fetch = schedule.fetch_version
            for t in range(schedule.n_steps):
                w = int(schedule.worker[t])
                self._dispatch.setdefault(w, deque()).append(
                    (t, int(fetch[t]), schedule.batch_rows[t]))

    # ------------------------------------------------------------- numerics

    def _hook_score(self, d_own, d_avg, prev_avg):
        import jax
        import jax.numpy as jnp

        with jax.enable_x64():
            return float(self.strategy.sim_score(
                jnp.float64(d_own), jnp.float64(d_avg), jnp.float64(prev_avg)))

    def _hook_replay(self, W2, lr):
        import jax
        import jax.numpy as jnp

        with jax.enable_x64():
            return np.asarray(self.strategy.sim_replay(
                jnp.asarray(W2), jnp.asarray(self.wscore),
                jnp.asarray(self.wgrads), jnp.float64(lr)))

    def _compensate(self, g, w_fetch):
        """Non-fused compensation (e.g. gap_aware) via the mesh hook, exactly
        as the scan body does for strategies without a kernel lambda."""
        import jax
        import jax.numpy as jnp
        from repro.engine.strategies import sim_shim_state

        with jax.enable_x64():
            shim = sim_shim_state(self.version, jnp.asarray(w_fetch),
                                  jnp.float64(self.prev_avg), self.spec.rho)
            return np.asarray(self.strategy.compensate_grads(
                jnp.asarray(g), jnp.asarray(self.W), shim))

    def _apply_opt(self, gt):
        spec = self.spec
        lr = spec.lr * self.lr_scale      # lr_scale == 1.0 until a rollback
        if spec.optimizer == "sgd":
            return self.W - lr * gt
        if spec.optimizer == "rmsprop":
            self.r = spec.rmsprop_beta * self.r + (1 - spec.rmsprop_beta) * gt * gt
            return self.W - lr * gt / np.sqrt(self.r + spec.eps)
        if spec.optimizer == "adagrad":
            self.r = self.r + gt * gt
            return self.W - lr * gt / np.sqrt(self.r + spec.eps)
        raise ValueError(spec.optimizer)

    def _apply_locked(self, g, read_version: int, rows, w_fetch,
                      wid: int = None) -> int:
        """One server step (caller holds the lock). Returns observed staleness.

        With a rollback-capable policy the post-apply validation loss is the
        divergence backstop: a finite-but-poisoned update that slipped the
        per-push screen trips here, the update is NOT committed (version does
        not advance — exactly-once applies and the staleness identity stay
        intact), and the store rolls back to the last verified state."""
        t = self.version
        s = t - int(read_version)
        g = np.asarray(g, np.float64)
        if w_fetch is None:
            w_fetch = self.W          # fresh push (staleness 0) or no-stale strategy
        if self.lam:
            gt = g + self.lam * g * g * (self.W - np.asarray(w_fetch, np.float64))
            g_window = g              # scan body stores the RAW gradient when fused
        else:
            g = self._compensate_maybe(g, w_fetch)
            gt = g_window = g
        loss_before = _loss(self.W, self.Xa[rows], self.y[rows]) if self.guided else 0.0
        W2 = self._apply_opt(gt)
        avg = _loss(W2, self.Xva, self.yv)
        if self.detector is not None and self.detector.update(avg):
            # poisoned trajectory: discard this update (the accumulator `r`
            # is restored by the rollback) and remediate
            self.diverged += 1
            self._rollback_locked(wid)
            return s
        if self.guided:
            d_avg = avg - self.prev_avg
            d_own = _loss(W2, self.Xa[rows], self.y[rows]) - loss_before
            sc = self._hook_score(d_own, d_avg, self.prev_avg)
            pos = t % self.rho
            self.wscore[pos] = sc
            self.wgrads[pos] = g_window
            if (t + 1) % self.rho == 0:
                W2 = self._hook_replay(W2, self.spec.lr)
                self.wscore[:] = 0.0
        self.W = W2
        self.prev_avg = avg
        self.version = t + 1
        if self.schedule is not None:
            self._ring[self.version] = W2.copy()
            for old in [v for v in self._ring if v < self.version - self._ring_keep]:
                del self._ring[old]
        self.history.append((self.version, avg))
        self.staleness.append(s)
        if self.detector is not None:
            # the committed state is by construction sane: the in-memory
            # rollback target when no verified disk snapshot exists
            self._good = (self.W.copy(), self.r.copy())
        if self._ckpt is not None and self._ckpt_every and self.version % self._ckpt_every == 0:
            self._snapshot()
        self.cond.notify_all()
        return s

    # ------------------------------------------------------------ resilience

    def _rollback_locked(self, wid=None):
        """Remediate a detected divergence (caller holds the lock): restore
        W/r from the newest VERIFIED checkpoint (sha-checked, falling back
        through manifest history) or the in-memory last-good copy, back the
        lr off, and quarantine the offending worker. The version counter is
        NEVER rewound — applies stay exactly-once and observed staleness
        stays `version - read_version`. Exhausting `max_rollbacks` marks the
        run fatal: workers drain on their next request, the launcher raises."""
        policy = self.policy
        self.rollbacks += 1
        if self.rollbacks > policy.max_rollbacks:
            self.fatal = RuntimeError(
                f"divergence persisted through {policy.max_rollbacks} "
                f"rollbacks (version {self.version}/{self.total}, "
                f"lr_scale {self.lr_scale:.3g}); the trajectory is not "
                f"recoverable by remediation")
            self.cond.notify_all()
            return
        restored_step = None
        W, r = self._good
        if self._ckpt is not None:
            from repro.checkpoint import CorruptCheckpointError, dist_restore

            try:
                snap = dist_restore(self.spec.ckpt_dir)
                W = snap["W"]
                r = snap.get("r", np.zeros_like(self.W))
                restored_step = int(snap["version"])
            except (FileNotFoundError, CorruptCheckpointError):
                pass  # nothing intact on disk (yet): in-memory last-good
        self.W = np.asarray(W, np.float64).copy()
        self.r = np.asarray(r, np.float64).copy()
        self.lr_scale *= policy.lr_backoff
        self.prev_avg = _loss(self.W, self.Xva, self.yv)
        if self.detector is not None:
            self.detector.best = min(self.detector.best, self.prev_avg)
        # the guided consistency window scored a trajectory that no longer
        # exists; restart it rather than replaying stale corrections
        self.wscore[:] = 0.0
        self.wgrads[:] = 0.0
        if wid is not None and self.screen is not None:
            self.screen.quarantine(wid, self.version)
        self.rollback_log.append((self.version, restored_step,
                                  "post-apply divergence"))
        self.cond.notify_all()

    def record_bad_frame(self, wid, exc) -> None:
        """A malformed/unparseable frame arrived on a worker connection: the
        chief drops the connection, counts it, and the run continues."""
        with self.cond:
            self.bad_frames += 1
            self.cond.notify_all()

    def record_reset(self) -> None:
        """A chaos-injected connection reset (repro.chaos): counted apart
        from organic worker exits so tests can assert the injection fired."""
        with self.cond:
            self.resets += 1
            self.cond.notify_all()

    def _held(self, wid) -> bool:
        """Whether `wid`'s step waits (caller holds the lock): the earliest
        planted fault whose version has come has not landed (its target is
        neither back nor banned), and is another worker's or a kill (the
        launcher makes it, the supervisor brings the target back)."""
        banned = lambda w: self.screen is not None and \
            self.version < self.screen.quarantined_until.get(w, -1)
        self._holds = [h for h in self._holds
                       if not (self._back.get(h[0], -1) >= h[1] or banned(h[0]))]
        due = sorted((at, w, kill) for w, at, kill in self._holds if self.version >= at)
        return (bool(due) and self.fatal is None and self.version < self.total
                and (due[0][2] or due[0][1] != wid))

    def fatal_error(self):
        with self.cond:
            return self.fatal

    def resilience_counters(self) -> dict:
        """The sentinel/remediation half of the launcher's `dist` result
        (supervisor stats merge in at the launcher)."""
        with self.cond:
            out = {
                "bad_frames": self.bad_frames,
                "resets": self.resets,
                "rollbacks": self.rollbacks,
                "diverged": self.diverged,
                "lr_scale": self.lr_scale,
                "rollback_log": list(self.rollback_log),
            }
            if self.screen is not None:
                out.update(self.screen.counters())
            return out

    def _compensate_maybe(self, g, w_fetch):
        from repro.engine.strategies import DelayCompensator

        if type(self.strategy).compensate_grads is DelayCompensator.compensate_grads:
            return g
        return self._compensate(g, w_fetch)

    # ------------------------------------------------------------ snapshots

    def _snapshot(self):
        from repro.checkpoint import dist_snapshot

        self._ckpt.save(self.version, dist_snapshot(
            self.W, self.version, np.asarray(self.staleness, np.int64),
            r=self.r, lr_scale=self.lr_scale))

    def final_snapshot(self):
        if self._ckpt is not None:
            with self.cond:
                self._snapshot()
            self._ckpt.close()

    # ---------------------------------------------------------- replay mode

    def replay_pull(self, wid: int):
        """Block until this worker's next scheduled fetch version exists, then
        serve the weights AS OF that version. None -> no dispatches left."""
        q = self._dispatch.get(wid)
        with self.cond:
            if not q:
                return None
            t, fetch_v, rows = q[0]
            self.cond.wait_for(lambda: self.version >= fetch_v)
            return self._ring[fetch_v], fetch_v, rows

    def replay_push(self, wid: int, g, read_version: int):
        """Block until the store reaches this dispatch's scheduled arrival
        step, then apply. Returns the observed staleness."""
        q = self._dispatch[wid]
        with self.cond:
            t, fetch_v, rows = q.popleft()
            self.cond.wait_for(lambda: self.version == t)
            w_fetch = self._ring[fetch_v] if self.need_fetch else None
            return self._apply_locked(g, read_version, rows, w_fetch)

    # ------------------------------------------------------------ live mode

    def live_step(self, wid: int, g, read_version: int, rows, w_fetch):
        """Apply a push (if any) and hand back the freshest params. Returns
        (W, version) or None once the step budget is exhausted (or the run
        went fatal — remediation exhausted — and workers should drain).

        With a sentinel policy the push is screened first: non-finite (and,
        at level "full", norm-exploded) gradients are rejected and counted
        per worker, never applied; a quarantined worker's pushes are ignored
        until its ban lifts, but it still receives fresh params — it may
        recover (a transient NaN source) without a respawn."""
        with self.cond:
            self.cond.wait_for(lambda: not self._held(wid))
            if self.fatal is not None:
                return None
            if g is not None:
                g = np.asarray(g, np.float64)
                if self.version >= self.total:
                    self.late += 1
                elif self.screen is not None and \
                        self.screen.admit(wid, g, self.version) is not None:
                    # rejected/quarantined: counted by the screen; a ban
                    # lands a hold (`_held`)
                    self.cond.notify_all()
                elif self.drop_rate and self._drop_rng.random() < self.drop_rate:
                    self.drops += 1
                else:
                    self._apply_locked(g, read_version, rows, w_fetch, wid=wid)
            if self.fatal is not None or self.version >= self.total:
                return None
            return self.W, self.version

    # --------------------------------------------------------- worker counts

    def record_join(self):
        """An elastic worker joined (chief assigned it a fresh wid)."""
        with self.cond:
            self.joins += 1

    def record_hello(self, wid):
        """A worker connected as `wid`; again: a fault of it has landed."""
        with self.cond:
            if wid in self._seen:
                self._back[wid] = self.version
            self._seen.add(wid)
            self.cond.notify_all()

    def record_worker_exit(self):
        """A worker connection died mid-stream (kill/crash): tolerated,
        counted, and waiters are woken so replay grants can re-examine."""
        with self.cond:
            self.worker_exits += 1
            self.cond.notify_all()

    # -------------------------------------------------------------- queries

    def done(self) -> bool:
        with self.cond:
            return self.version >= self.total

    def progress(self) -> int:
        with self.cond:
            return self.version

    def staleness_hist(self) -> dict:
        with self.cond:
            staleness = list(self.staleness)
        counts = np.bincount(np.asarray(staleness, np.int64)) if staleness else []
        return {int(s): int(n) for s, n in enumerate(counts) if n}
