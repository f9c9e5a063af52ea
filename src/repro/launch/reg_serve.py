"""Cohort registration server: keep one jitted Newton step hot, stream jobs
through its subject slots.

    PYTHONPATH=src python -m repro.launch.reg_serve --jobs 6 --slots 3 \
        --size 16 --beta 1e-2 --max-newton 8

The economics (ROADMAP "solves/second" item): on a mesh, one registration
solve pays a fixed collective-latency bill per Newton iteration (ghost
exchanges + pencil all-to-alls) that is independent of how many subjects
ride the batched kernels.  ``gn.solve_cohort`` amortizes that bill across a
fixed cohort; this driver amortizes it across an UNBOUNDED job stream:

* jobs are bucketed by ``(image shape, GNConfig)`` — each bucket owns ONE
  ``gn.make_cohort_step`` executable (image stacks, the continuation beta,
  per-subject forcing references, and the active mask are all traced
  arguments, so admissions/retirements NEVER recompile; pinned by
  ``tests/test_cohort.py``);
* each bucket runs an S-slot cohort: per-subject masked termination retires
  a converged subject mid-flight and its slot is refilled from the queue on
  the next iteration, so the executable keeps running near-full cohorts
  instead of waiting for stragglers;
* per-subject accounting: every job is billed exactly the Hessian matvecs
  its own masked PCG consumed (``fine_equiv_matvecs``; a slot's meter is
  zero while it hosts a retired/free subject), so the cohort batching is
  cost-transparent per job — the paper's Table V metric, per subject.

Slot refills require every subject in a bucket to share one regularization
scalar per step (``beta`` is a single traced scalar, not per-subject), so a
server config must not use ``beta_continuation`` — run continuation as
separate buckets, coarse-beta bucket feeding the fine-beta bucket's queue.

Resilience (ISSUE 10): every retirement carries an explicit ``status``
reason read off the in-graph health guard (``repro.resilience.health``);
``serve_jobs(retry=RetryPolicy(...))`` re-admits failed jobs under a
degradation ladder (a beta-only rung re-uses the failing bucket's compiled
executable); ``serve_jobs(checkpoint=dir)`` snapshots the whole session
through ``ckpt.manager.CheckpointManager`` and ``resume=True`` restarts a
killed stream re-serving only unfinished jobs with per-job billing
preserved.  ``CohortServer.hooks`` is the fault-injection surface
(``repro.resilience.faults``).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import gauss_newton as gn
from repro.core.grid import Grid, make_grid
from repro.core.spectral import SpectralOps
from repro.launch.compile_cache import enable_compile_cache
from repro.resilience import health
from repro.resilience import policy as res_policy

_FORCING_SENTINEL = 1e-30  # first iteration of a subject: eta = eta_max


@dataclasses.dataclass
class RegJob:
    """One registration request: a reference/template image pair.

    ``v0`` optionally warm-starts the slot (``repro.blocks`` admits every
    tile with the prolonged global coarse velocity); ``g0_ref`` optionally
    fixes the CONVERGENCE reference gradient norm — a warm-started job
    passes its cold-start norm so it terminates at the same absolute
    tolerance a cold solve would, exactly the ``gn.solve(g0_ref=...)``
    semantics of the multilevel ladder (the Eisenstat-Walker forcing
    reference stays decoupled: it is always the slot's first iterate).
    ``block`` tags the job's tile index for per-block ``JobEvent`` billing.
    """

    job_id: Any
    rho_R: jnp.ndarray  # (N1, N2, N3)
    rho_T: jnp.ndarray
    v0: jnp.ndarray | None = None  # (3, N..) warm start; None = zero
    g0_ref: float | None = None
    block: tuple | None = None
    attempt: int = 1  # 1 = original admission; >1 = a degraded retry


@dataclasses.dataclass
class JobResult:
    job_id: Any
    v: np.ndarray  # (3, N..) converged velocity
    newton_iters: int
    hessian_matvecs: int
    fine_equiv_matvecs: float  # single level: == hessian_matvecs
    rel_gnorm: float
    converged: bool  # rel_gnorm <= gtol (kept for back-compat with status)
    # explicit retirement reason — what ``converged=False`` used to
    # conflate: "converged" | "stagnated" | "max_newton" | "nonfinite" |
    # "diverged" | "pcg_breakdown" (``repro.resilience.health`` names)
    status: str = ""
    attempts: int = 1  # serve attempt that produced this result
    # the carried ``Lap^2 v`` of the retired slot (``gn.Iterate``), left on
    # the device, on the result ``CohortServer.step`` returns; None in
    # ``CohortServer.results``, in ``serve_jobs``' results and where the
    # result was rebuilt from a checkpoint
    lap2: Any = None


class CohortServer:
    """One executable bucket: an S-slot cohort over a fixed (grid, cfg).

    ``step()`` advances every live slot one masked Newton iteration and
    returns the jobs that retired; ``admit()`` queues jobs; ``run()`` drives
    the loop until queue and slots drain.  Pass ``ops``/``interp`` from a
    ``DistContext`` to serve on a mesh.
    """

    def __init__(self, grid: Grid, cfg: gn.GNConfig, slots: int = 4,
                 ops: SpectralOps | None = None, interp=None, step_fn=None):
        if cfg.beta_continuation:
            raise ValueError(
                "CohortServer slots share one traced beta per step; run "
                "beta continuation as chained server buckets instead"
            )
        self.grid, self.cfg, self.slots = grid, cfg, slots
        self.step_fn = step_fn or gn.make_cohort_step(grid, cfg, ops=ops, interp=interp)
        self.queue: list[RegJob] = []
        self.results: list[JobResult] = []
        S = slots
        self._jobs: list[RegJob | None] = [None] * S
        self._v = jnp.zeros((S, 3) + grid.shape, grid.dtype)
        # Lap^2 of each slot's iterate, carried exactly (gn.with_lap2)
        self._lap2 = jnp.zeros_like(self._v)
        self._ops = ops
        self._rho_R = jnp.zeros((S,) + grid.shape, grid.dtype)
        self._rho_T = jnp.zeros((S,) + grid.shape, grid.dtype)
        self._g_forcing = np.full(S, _FORCING_SENTINEL, np.float32)
        self._g0 = np.zeros(S, np.float32)  # termination reference per slot
        self._g0_preset = np.zeros(S, bool)  # True: job supplied g0_ref
        self._newton = np.zeros(S, np.int64)
        self._cg = np.zeros(S, np.int64)
        self._rel = np.zeros(S, np.float32)
        self.iterations = 0  # cohort step calls (the shared-cost meter)
        self.refills = 0  # slot fills after a retirement (not initial fills)
        self.admitted = 0  # total jobs ever admitted to this bucket
        self._echo = False  # run(verbose=...) renders retirements via telemetry
        self._enqueued_at: dict[int, int] = {}  # id(job) -> iterations at admit
        self._admitted_at = np.zeros(S, np.int64)  # iterations at slot fill
        self._queue_wait = np.zeros(S, np.int64)  # steps spent queued
        # fault-injection surface: callables invoked with this server at the
        # top of every step() (repro.resilience.faults hooks mutate slot
        # state or abort the loop host-side; the compiled step is untouched)
        self.hooks: list = []

    def admit(self, *jobs: RegJob) -> None:
        for job in jobs:
            self._enqueued_at[id(job)] = self.iterations
        self.admitted += len(jobs)
        self.queue.extend(jobs)

    @property
    def active(self) -> np.ndarray:
        return np.asarray([j is not None for j in self._jobs])

    def _fill_slots(self) -> None:
        with telemetry.span("serve.fill"):
            for s in range(self.slots):
                if self._jobs[s] is None and self.queue:
                    job = self.queue.pop(0)
                    self._jobs[s] = job
                    if job.v0 is None:
                        self._v = self._v.at[s].set(0.0)
                        self._lap2 = self._lap2.at[s].set(0.0)
                    else:
                        v0, lap2 = gn.with_lap2(jnp.asarray(job.v0, self.grid.dtype),
                                                self._ops or SpectralOps(self.grid))
                        self._v = self._v.at[s].set(v0)
                        self._lap2 = self._lap2.at[s].set(lap2)
                    self._rho_R = self._rho_R.at[s].set(jnp.asarray(job.rho_R))
                    self._rho_T = self._rho_T.at[s].set(jnp.asarray(job.rho_T))
                    self._g_forcing[s] = _FORCING_SENTINEL
                    self._g0_preset[s] = job.g0_ref is not None
                    self._g0[s] = job.g0_ref if job.g0_ref is not None else 0.0
                    self._newton[s] = 0
                    self._cg[s] = 0
                    if self.iterations > 0:
                        self.refills += 1
                    self._admitted_at[s] = self.iterations
                    self._queue_wait[s] = self.iterations - self._enqueued_at.pop(
                        id(job), self.iterations
                    )

    def _retire(self, s: int, converged: bool, status: str) -> JobResult:
        job = self._jobs[s]
        res = JobResult(
            job_id=job.job_id,
            v=np.asarray(self._v[s]),
            newton_iters=int(self._newton[s]),
            hessian_matvecs=int(self._cg[s]),
            fine_equiv_matvecs=float(self._cg[s]),
            rel_gnorm=float(self._rel[s]),
            converged=converged,
            status=status,
            attempts=int(job.attempt),
            lap2=self._lap2[s],
        )
        self._jobs[s] = None
        # only the result ``step`` returns holds ``lap2``: the server's record
        # keeps none, so device memory does not grow with the jobs retired
        self.results.append(dataclasses.replace(res, lap2=None))
        if status in health.FAILED_NAMES:
            telemetry.counter(
                "resilience.guard_tripped", status=status, source="reg_serve"
            )
        # the per-tenant billing record (the paper's Table V meter, per job)
        telemetry.emit(
            telemetry.JobEvent(
                job_id=str(res.job_id),
                newton_iters=res.newton_iters,
                hessian_matvecs=res.hessian_matvecs,
                fine_equiv_matvecs=res.fine_equiv_matvecs,
                rel_gnorm=res.rel_gnorm,
                converged=res.converged,
                slot=s,
                queue_wait_steps=int(self._queue_wait[s]),
                admitted_step=int(self._admitted_at[s]),
                retired_step=self.iterations,
                block=list(job.block) if job.block is not None else None,
                status=res.status,
                attempts=res.attempts,
            ),
            echo=self._echo,
        )
        return res

    def step(self) -> list[JobResult]:
        """Fill free slots, advance one masked Newton iteration, retire.

        A profiler trace shows the step as the host span ``serve.step``
        with the children ``serve.fill`` (slot writes), ``serve.dispatch``
        (the compiled step's call and its argument transfers),
        ``serve.read_log`` (the pulls of the step's log, which wait for the
        device) and ``serve.retire`` (host bookkeeping and the retired
        velocities' pulls)."""
        with telemetry.span("serve.step"):
            return self._step()

    def _step(self) -> list[JobResult]:
        for hook in list(self.hooks):
            hook(self)
        self._fill_slots()
        active = self.active
        if not active.any():
            return []
        with telemetry.span("serve.dispatch"):
            (self._v, self._lap2), log = self.step_fn(
                gn.Iterate(self._v, self._lap2),
                jnp.asarray(self._g_forcing),
                jnp.asarray(active),
                jnp.float32(self.cfg.beta),
                self._rho_R,
                self._rho_T,
            )
        self.iterations += 1
        with telemetry.span("serve.read_log"):
            gnorm = np.asarray(log.gnorm, np.float32)
            step_len = np.asarray(log.step_len)
            code = np.asarray(log.status, np.int64)
            cg_iters = np.asarray(log.cg_iters, np.int64)
        with telemetry.span("serve.retire"):
            self._newton += active
            self._cg += cg_iters
            retired = []
            for s in range(self.slots):
                if not active[s]:
                    continue
                # a freshly admitted subject's first iterate fixes its
                # Eisenstat-Walker forcing reference, and — unless the job
                # supplied an explicit g0_ref (warm-started blocks do) — its
                # termination reference (the decoupling of gn.solve, per slot)
                if self._g_forcing[s] == _FORCING_SENTINEL:
                    self._g_forcing[s] = gnorm[s]
                    if not self._g0_preset[s]:
                        self._g0[s] = gnorm[s]
                self._rel[s] = gnorm[s] / max(self._g0[s], _FORCING_SENTINEL)
                converged = bool(self._rel[s] <= self.cfg.gtol)
                # retirement reason: the in-graph guard decides the failure
                # modes; the host decides converged / stagnated / max_newton
                if int(code[s]) in health.FAILED_CODES:
                    status = health.status_name(int(code[s]))
                    converged = False
                elif converged:
                    status = health.status_name(health.CONVERGED)
                elif step_len[s] == 0.0:
                    status = health.status_name(health.STAGNATED)
                elif self._newton[s] >= self.cfg.max_newton:
                    status = health.status_name(health.MAX_NEWTON)
                else:
                    continue
                retired.append(self._retire(s, converged, status))
            telemetry.emit(
                telemetry.ServeStepEvent(
                    iteration=self.iterations,
                    slots=self.slots,
                    occupancy=int(active.sum()),
                    queue_len=len(self.queue),
                    refills=self.refills,
                )
            )
            return retired

    def run(self, verbose: bool = False) -> list[JobResult]:
        self._echo = verbose
        try:
            while self.queue or self.active.any():
                self.step()
        finally:
            self._echo = False
        return self.results

    def compiled_executables(self) -> int:
        return int(self.step_fn._cache_size())

    # ------------------------------------------------------------------ #
    # checkpointed job streams: the snapshot is standalone — it carries the
    # slot state AND every queued job's images, so ``restore`` needs no
    # access to the original job list (job_ids must be JSON-serializable)
    def snapshot(self) -> tuple[dict, dict]:
        """(tree, meta) for ``ckpt.manager.CheckpointManager.save``: arrays
        in the tree, JSON-able bookkeeping in the meta."""
        zero_v = jnp.zeros((3,) + self.grid.shape, self.grid.dtype)
        tree = {
            "v": self._v,
            "lap2": self._lap2,
            "rho_R": self._rho_R,
            "rho_T": self._rho_T,
            "queue_rho_R": [jnp.asarray(j.rho_R) for j in self.queue],
            "queue_rho_T": [jnp.asarray(j.rho_T) for j in self.queue],
            "queue_v0": [
                zero_v if j.v0 is None else jnp.asarray(j.v0) for j in self.queue
            ],
        }

        def _job_meta(job: RegJob) -> dict:
            return {
                "job_id": job.job_id,
                "attempt": int(job.attempt),
                "g0_ref": None if job.g0_ref is None else float(job.g0_ref),
                "block": None if job.block is None else list(job.block),
            }

        meta = {
            "iterations": int(self.iterations),
            "refills": int(self.refills),
            "admitted": int(self.admitted),
            "slot_jobs": [
                None
                if job is None
                else {
                    **_job_meta(job),
                    "g_forcing": float(self._g_forcing[s]),
                    "g0": float(self._g0[s]),
                    "g0_preset": bool(self._g0_preset[s]),
                    "newton": int(self._newton[s]),
                    "cg": int(self._cg[s]),
                    "rel": float(self._rel[s]),
                    "admitted_at": int(self._admitted_at[s]),
                    "queue_wait": int(self._queue_wait[s]),
                }
                for s, job in enumerate(self._jobs)
            ],
            "queue_jobs": [
                {
                    **_job_meta(job),
                    "has_v0": job.v0 is not None,
                    "enqueued_at": int(
                        self._enqueued_at.get(id(job), self.iterations)
                    ),
                }
                for job in self.queue
            ],
        }
        return tree, meta

    @classmethod
    def restore(cls, grid: Grid, cfg: gn.GNConfig, tree: dict, meta: dict,
                ops: SpectralOps | None = None, interp=None, step_fn=None
                ) -> "CohortServer":
        """Rebuild a server mid-stream from a ``snapshot()`` pair.  Slot
        iterates, per-slot billing meters, and queued jobs (images included)
        all resume exactly; only unfinished jobs are re-served."""
        srv = cls(grid, cfg, slots=len(meta["slot_jobs"]), ops=ops,
                  interp=interp, step_fn=step_fn)
        srv._v = jnp.asarray(tree["v"], grid.dtype)
        if "lap2" in tree:
            srv._lap2 = jnp.asarray(tree["lap2"], grid.dtype)
        else:  # a snapshot from before the image was carried: transform once
            srv._lap2 = gn.with_lap2(srv._v, ops or SpectralOps(grid)).lap2
        srv._rho_R = jnp.asarray(tree["rho_R"], grid.dtype)
        srv._rho_T = jnp.asarray(tree["rho_T"], grid.dtype)
        srv.iterations = int(meta["iterations"])
        srv.refills = int(meta["refills"])
        srv.admitted = int(meta["admitted"])
        for s, sm in enumerate(meta["slot_jobs"]):
            if sm is None:
                continue
            srv._jobs[s] = RegJob(
                job_id=sm["job_id"],
                rho_R=srv._rho_R[s],
                rho_T=srv._rho_T[s],
                v0=None,
                g0_ref=sm["g0_ref"],
                block=None if sm["block"] is None else tuple(sm["block"]),
                attempt=int(sm["attempt"]),
            )
            srv._g_forcing[s] = sm["g_forcing"]
            srv._g0[s] = sm["g0"]
            srv._g0_preset[s] = sm["g0_preset"]
            srv._newton[s] = sm["newton"]
            srv._cg[s] = sm["cg"]
            srv._rel[s] = sm["rel"]
            srv._admitted_at[s] = sm["admitted_at"]
            srv._queue_wait[s] = sm["queue_wait"]
        for q, qm in enumerate(meta["queue_jobs"]):
            job = RegJob(
                job_id=qm["job_id"],
                rho_R=jnp.asarray(tree["queue_rho_R"][q], grid.dtype),
                rho_T=jnp.asarray(tree["queue_rho_T"][q], grid.dtype),
                v0=jnp.asarray(tree["queue_v0"][q], grid.dtype)
                if qm["has_v0"]
                else None,
                g0_ref=qm["g0_ref"],
                block=None if qm["block"] is None else tuple(qm["block"]),
                attempt=int(qm["attempt"]),
            )
            srv.queue.append(job)
            srv._enqueued_at[id(job)] = int(qm["enqueued_at"])
        return srv


def _result_meta(res: JobResult) -> dict:
    """JSON-able billing fields of a JobResult (the ``v`` array rides the
    checkpoint tree separately)."""
    return {
        "job_id": res.job_id,
        "newton_iters": int(res.newton_iters),
        "hessian_matvecs": int(res.hessian_matvecs),
        "fine_equiv_matvecs": float(res.fine_equiv_matvecs),
        "rel_gnorm": float(res.rel_gnorm),
        "converged": bool(res.converged),
        "status": res.status,
        "attempts": int(res.attempts),
    }


def serve_jobs(jobs: list[RegJob], cfg: gn.GNConfig, slots: int = 4,
               ops: SpectralOps | None = None, interp=None,
               verbose: bool = False,
               retry: "res_policy.RetryPolicy | None" = None,
               checkpoint: Any = None, checkpoint_every: int = 5,
               resume: bool = False, faults: list | None = None,
               grid_dtype=None) -> dict:
    """Bucket ``jobs`` by (image shape, attempt) and drain every bucket.

    Returns ``{"results": [JobResult...], "buckets": {key: stats},
    "compiled_executables": n}``.  A bucket key is ``tuple(shape)`` for the
    primary attempt and ``tuple(shape) + ("retry<k>",)`` for degraded
    retries; ``compiled_executables`` counts distinct compiled step
    programs over the whole session (1 when every retry rode a beta-only
    rung).

    * ``retry``: a ``repro.resilience.RetryPolicy`` — jobs retiring with a
      status in ``retry.retry_on`` are re-admitted under the degradation
      ladder, warm-started from their last good iterate when finite.
    * ``checkpoint``: a directory (or ``CheckpointManager``) snapshotting
      the whole session every ``checkpoint_every`` serve rounds; with
      ``resume=True`` the latest snapshot is restored and ONLY unfinished
      jobs are re-served (``jobs`` is ignored when a snapshot exists —
      the snapshot carries every queued image and completed result).
    * ``faults``: fault-injection hooks attached to every server
      (``repro.resilience.faults``); deterministic chaos for the tests.
    * ``grid_dtype``: dtype for the per-bucket grids (``repro.blocks``
      serves tiles of the global grid's dtype).
    """
    faults = list(faults or [])
    mgr = None
    if checkpoint is not None:
        from repro.ckpt.manager import CheckpointManager

        mgr = (
            checkpoint
            if isinstance(checkpoint, CheckpointManager)
            else CheckpointManager(checkpoint)
        )

    step_cache: dict = {}  # (shape, static_key(cfg)) -> shared jitted step
    servers: dict[tuple, CohortServer] = {}  # (shape, attempt) -> server
    by_id: dict = {}  # job_id -> RegJob (images for retry re-admission)
    final: list[JobResult] = []  # one final result per job

    def _bucket_cfg(attempt: int) -> gn.GNConfig:
        if retry is not None and attempt > 1:
            return retry.degraded(cfg, attempt)
        return cfg

    def _make_grid(shape):
        return make_grid(shape, grid_dtype) if grid_dtype is not None else make_grid(shape)

    def _get_server(shape, attempt: int) -> CohortServer:
        key = (tuple(shape), int(attempt))
        if key not in servers:
            grid = _make_grid(key[0])
            cfg_a = _bucket_cfg(key[1])
            sk = (key[0], res_policy.static_key(cfg_a))
            if sk not in step_cache:
                step_cache[sk] = gn.make_cohort_step(grid, cfg_a, ops=ops, interp=interp)
            srv = CohortServer(grid, cfg_a, slots=slots, ops=ops, interp=interp,
                               step_fn=step_cache[sk])
            srv.hooks.extend(faults)
            servers[key] = srv
        return servers[key]

    def _restore_server(shape, attempt: int, tree: dict, meta: dict) -> CohortServer:
        key = (tuple(shape), int(attempt))
        grid = _make_grid(key[0])
        cfg_a = _bucket_cfg(key[1])
        sk = (key[0], res_policy.static_key(cfg_a))
        if sk not in step_cache:
            step_cache[sk] = gn.make_cohort_step(grid, cfg_a, ops=ops, interp=interp)
        srv = CohortServer.restore(grid, cfg_a, tree, meta, ops=ops, interp=interp,
                                   step_fn=step_cache[sk])
        srv.hooks.extend(faults)
        servers[key] = srv
        return srv

    # ---- session bring-up: resume from the latest snapshot, or admit jobs
    serve_round = 0
    restored = False
    if resume and mgr is not None and mgr.latest_step() is not None:
        tree, meta = mgr.restore()
        serve_round = int(meta["step"])
        for r_meta, r_v in zip(meta["results"], tree["results_v"]):
            final.append(JobResult(v=np.asarray(r_v), **r_meta))
        for label, bm in meta["buckets"].items():
            _restore_server(tuple(bm["shape"]), int(bm["attempt"]),
                            tree["buckets"][label], bm)
        for srv in servers.values():
            for j in list(srv.queue) + [x for x in srv._jobs if x is not None]:
                by_id.setdefault(j.job_id, j)
        restored = True
        telemetry.emit(
            telemetry.RecoveryEvent(
                action="resume_from_checkpoint",
                step=serve_round,
                attrs={
                    "completed": len(final),
                    "unfinished": sum(
                        len(s.queue) + int(s.active.sum()) for s in servers.values()
                    ),
                },
            ),
            echo=verbose,
        )
        telemetry.counter("resilience.resumes")
    if not restored:
        for job in jobs:
            by_id[job.job_id] = job
            _get_server(np.shape(job.rho_R), job.attempt).admit(job)

    # ---- retirement handling: retry failed jobs through the ladder -------
    def _handle(res: JobResult) -> None:
        if (
            retry is not None
            and res.status in retry.retry_on
            and res.attempts < retry.max_attempts
            and res.job_id in by_id
        ):
            base = by_id[res.job_id]
            v_last = np.asarray(res.v)
            warm = retry.warm_start and bool(np.isfinite(v_last).all())
            nxt = res.attempts + 1
            rj = RegJob(
                job_id=res.job_id,
                rho_R=base.rho_R,
                rho_T=base.rho_T,
                v0=v_last if warm else base.v0,
                g0_ref=base.g0_ref,
                block=base.block,
                attempt=nxt,
            )
            by_id[res.job_id] = rj
            _get_server(np.shape(base.rho_R), nxt).admit(rj)
            telemetry.emit(
                telemetry.RecoveryEvent(
                    action="retry_degraded",
                    job_id=str(res.job_id),
                    attempts=nxt,
                    attrs={"status": res.status, "warm_start": warm},
                ),
                echo=verbose,
            )
            telemetry.counter("resilience.retries", status=res.status)
            return
        if res.status in health.FAILED_NAMES:
            telemetry.counter("resilience.jobs_failed", status=res.status)
        final.append(dataclasses.replace(res, lap2=None))

    def _save_session() -> None:
        tree: dict = {"buckets": {}, "results_v": [jnp.asarray(r.v) for r in final]}
        meta: dict = {"buckets": {}, "results": [_result_meta(r) for r in final]}
        for (shape, attempt), srv in servers.items():
            label = "x".join(map(str, shape)) + f"@a{attempt}"
            t, m = srv.snapshot()
            tree["buckets"][label] = t
            meta["buckets"][label] = {"shape": list(shape), "attempt": attempt, **m}
        mgr.save(serve_round, tree, meta)

    # ---- drain loop: round-robin over buckets, periodic snapshots --------
    def _live(srv: CohortServer) -> bool:
        return bool(srv.queue) or bool(srv.active.any())

    while any(_live(s) for s in servers.values()):
        for key in list(servers):
            srv = servers[key]
            if not _live(srv):
                continue
            srv._echo = verbose
            try:
                for res in srv.step():
                    _handle(res)
            finally:
                srv._echo = False
        serve_round += 1
        if mgr is not None and checkpoint_every and serve_round % checkpoint_every == 0:
            _save_session()
    if mgr is not None:
        _save_session()

    # ---- stats: per-bucket meters + the session-wide executable count ----
    stats: dict = {}
    execs: dict[int, int] = {}
    for (shape, attempt), srv in servers.items():
        key = shape if attempt == 1 else shape + (f"retry{attempt}",)
        stats[key] = {
            "jobs": srv.admitted,
            "attempt": attempt,
            "cohort_iterations": srv.iterations,
            "compiled_executables": srv.compiled_executables(),
        }
        execs[id(srv.step_fn)] = srv.compiled_executables()
    return {
        "results": final,
        "buckets": stats,
        "compiled_executables": sum(execs.values()),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--jobs", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--size", type=int, default=16)
    ap.add_argument("--beta", type=float, default=1e-2)
    ap.add_argument("--n-t", type=int, default=4)
    ap.add_argument("--max-newton", type=int, default=8)
    ap.add_argument("--max-cg", type=int, default=30)
    ap.add_argument("--gtol", type=float, default=1e-2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", type=str, default=None,
                    help="write a telemetry JSONL trace to this path "
                         "(render with: python -m repro.analysis.trace_report)")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.data.synthetic import synthetic_problem

    cfg = gn.GNConfig(beta=args.beta, n_t=args.n_t, max_newton=args.max_newton,
                      max_cg=args.max_cg, gtol=args.gtol)
    rng = np.random.default_rng(args.seed)
    jobs = []
    for j in range(args.jobs):
        amp = float(rng.uniform(0.3, 1.0))
        rho_R, rho_T, _, _ = synthetic_problem(args.size, n_t=args.n_t, amplitude=amp)
        jobs.append(RegJob(job_id=f"job{j}(amp={amp:.2f})", rho_R=rho_R, rho_T=rho_T))

    import contextlib

    sink = telemetry.jsonl_sink(args.trace) if args.trace else contextlib.nullcontext()
    t0 = time.time()
    with sink:
        out = serve_jobs(jobs, cfg, slots=args.slots, verbose=True)
    dt = time.time() - t0
    for shape, st in out["buckets"].items():
        print(
            f"bucket {shape}: {st['jobs']} jobs in {st['cohort_iterations']} cohort "
            f"iterations, {st['compiled_executables']} compiled executable(s)"
        )
    total_mv = sum(r.hessian_matvecs for r in out["results"])
    print(f"served {len(out['results'])} jobs in {dt:.1f}s, {total_mv} matvecs total")


if __name__ == "__main__":
    main()
