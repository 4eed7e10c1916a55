"""Training driver: end-to-end loop with UDS scheduling, checkpoints,
straggler mitigation.

CPU-runnable (smoke configs / reduced settings); the same driver targets
TPU pods by picking a production mesh and full config:

    python -m repro.launch.train --arch qwen2.5-3b --smoke --steps 50
    python -m repro.launch.train --arch qwen3-moe-235b-a22b --smoke \
        --steps 30 --scheduler awf --microbatches 2

Multi-host (``hosts > 1``): the loop runs on a ``("host", "model")`` mesh
(emulate N hosts on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the first
jax import), records PER-HOST step wall times into the telemetry ledger,
feeds them through ``StragglerMitigator.observe_step`` every step, and on
each measured-epoch bump re-splits the global batch UNEVENLY across hosts
from the mitigator's AWF ``token_shares`` (``split_batch_by_shares`` —
masked, shape-static).  A slow host (``host_skew`` injects one in
emulation; real pods report real clocks) sees its token share shrink
within a few steps:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    python -m repro.launch.train --arch qwen2.5-3b --smoke --hosts 4 \
        --straggler-scheduler "wf2"
"""

from __future__ import annotations

import argparse
import time
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core import (Chunk, LoopHistory, LoopTelemetry, MembershipEvent,
                        get_engine)
from repro.core.spec import SpecLike, resolve
from repro.data import SyntheticCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (batch_shardings, make_host_mesh, make_mesh,
                               rules_for, shardings_for)
from repro.launch.steps import (apply_microbatch_plan, make_fused_train_step,
                                make_train_step, opt_state_specs,
                                plan_microbatches, split_batch_by_shares)
from repro.models import get_model
from repro.optim import cosine_schedule, make_optimizer, wsd_schedule
from repro.sched import (CapacityPlanner, StragglerMitigator,
                         pack_with_scheduler)
from repro.sched.microbatch import (plan_hier_microbatch_permutation,
                                    plan_microbatch_permutation)
from repro.sharding import axis_rules
from repro.checkpoint import AsyncCheckpointer

__all__ = ["TrainLoop", "main"]


class TrainLoop:
    """Composable training loop; examples and tests drive this class."""

    def __init__(self, cfg, *, batch: int, seq_len: int,
                 mesh_shape=None, scheduler: SpecLike = "fac2",
                 microbatch_scheduler: SpecLike = "dynamic,1",
                 num_microbatches: int = 1,
                 fused_microbatches: bool = False, lr: float = 3e-4,
                 ckpt_dir: Optional[str] = None, seed: int = 0,
                 data_sigma: float = 1.0, hosts: int = 1,
                 straggler_scheduler: SpecLike = "wf2",
                 min_host_share: float = 0.1,
                 host_skew: Optional[Sequence[float]] = None,
                 elastic: bool = False,
                 kill_hosts: Optional[Sequence[int]] = None,
                 kill_at_step: Optional[int] = None):
        self.cfg = cfg
        self.batch, self.seq_len = batch, seq_len
        self.model = get_model(cfg)
        self.history = LoopHistory()
        if hosts < 1:
            raise ValueError(f"hosts must be >= 1, got {hosts}")
        if batch % hosts != 0:
            raise ValueError(f"global batch {batch} not divisible by "
                             f"{hosts} hosts")
        # ``scheduler`` accepts any schedule clause form, including a
        # hierarchical composition hier(host=..., device=..., tile=...).
        # A hier clause threads through every loop surface: the outermost
        # (host) level packs documents and drives the straggler token
        # shares, the device level assigns microbatch rows per host block.
        self.pack_sched = resolve(scheduler)
        self.hier = (self.pack_sched
                     if getattr(self.pack_sched, "hier_levels", None)
                     else None)
        if hosts > 1 and num_microbatches > 1:
            if self.hier is None:
                # the splitter's host model is "host h owns contiguous row
                # block h" of the (B, S) input; the microbatch reshape
                # (B,S) -> (M, B/M, S) inside jit lets GSPMD re-shard each
                # microbatch over the hosts, so for a FLAT clause physical
                # row ownership is no longer that block and shares /
                # attribution would land on the wrong hosts.  A hier
                # clause's host level owns the blocks and the microbatch
                # permutation is planned PER BLOCK, interleaved so every
                # microbatch's host-h shard holds only host-h rows
                # (plan_hier_microbatch_permutation).
                raise ValueError(
                    "hosts > 1 does not compose with num_microbatches > 1 "
                    "for a flat schedule clause — use a hierarchical one, "
                    "e.g. hier(host=awf, device=static) "
                    "(docs/SCHEDULING.md, Hierarchical composition)")
            if (batch // hosts) % num_microbatches != 0:
                raise ValueError(
                    f"per-host row block ({batch // hosts}) not divisible "
                    f"by num_microbatches ({num_microbatches})")
        self.hosts = hosts
        # per-host slowdown multipliers — the EMULATION's measurement model
        # (one process cannot clock N emulated hosts separately): host h's
        # share of each step's wall time is token_count[h] * host_skew[h].
        # Real multi-host deployments pass genuine per-host clocks to
        # ``mitigator.observe_step`` instead and leave this at ones.
        skew = np.ones(hosts) if host_skew is None else np.asarray(
            host_skew, float)
        if skew.shape != (hosts,) or not (skew > 0).all():
            raise ValueError(f"host_skew needs {hosts} positive entries")
        self.host_skew = skew
        # the measure stage: per-step wall time + token counts flushed into
        # the history under "train_step" — each flush bumps the measured
        # epoch, so adaptive schedules planning against this history replan
        # from real step times (and the packing history's own records feed
        # the AWF document packer).  Multi-host: one ledger per host, the
        # step's wall time split by ``add_time_weighted`` attribution.
        self.telemetry = LoopTelemetry(self.history, loop_id="train_step",
                                       num_workers=hosts)
        # ``microbatch_scheduler`` accepts any schedule clause form: a
        # spec, "guided,4", "uds:name(args)", "runtime", or a scheduler
        # instance.  A hier clause's device level (when present) takes
        # over the microbatch assignment.
        dev_level = self.hier.level("device") if self.hier else None
        self.microbatch_sched = (dev_level if dev_level is not None
                                 else microbatch_scheduler)
        self.num_microbatches = num_microbatches
        # fused: apply the UDS microbatch permutation ON DEVICE inside the
        # jitted step (one dispatch per optimizer step) instead of as a
        # host-side eager gather before it — numerically identical
        # (same permutation, lowered into the program).  A no-op request
        # at num_microbatches == 1 is simply ignored.
        self.fused_microbatches = bool(fused_microbatches
                                       and num_microbatches > 1)
        self.capacity = (CapacityPlanner(cfg, seq_len) if cfg.is_moe else None)

        devs = len(jax.devices())
        if hosts > 1:
            if devs < hosts:
                raise ValueError(
                    f"hosts={hosts} needs {hosts} devices, only {devs} "
                    f"available — emulate them with XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={hosts} "
                    f"(before the first jax import)")
            if mesh_shape is not None:
                if mesh_shape[0] != hosts:
                    raise ValueError(f"mesh_shape {tuple(mesh_shape)} "
                                     f"disagrees with hosts={hosts}")
                model_par = mesh_shape[1]
            else:
                model_par = 1
                per_host = devs // hosts
                while model_par * 2 <= per_host and model_par < 4:
                    model_par *= 2
            self.mesh = make_host_mesh(hosts, model_par)
        else:
            if mesh_shape is None:
                model_par = 1
                while model_par * 2 <= devs and model_par < 4:
                    model_par *= 2
                mesh_shape = (max(devs // model_par, 1), model_par)
            else:
                model_par = mesh_shape[-1]
            self.mesh = make_mesh(mesh_shape, ("data", "model"))
        self.model_par = model_par
        self.rules = rules_for(cfg, self.mesh, "train", batch)
        # elastic scheduling: membership change (worker loss) becomes a
        # replan event — see apply_membership().  The original clause
        # strings are kept so the active specs can be RE-RESOLVED over the
        # new team size after churn (auto reselects from fresh telemetry).
        self.elastic = bool(elastic)
        self._scheduler_clause = scheduler
        # a hierarchical --scheduler owns the host-share policy too: the
        # mitigator plans the FULL hier clause (its worker_iters are the
        # host level's shares, and the ComposedPlan's provenance is what a
        # membership requeue recovers a dead host's block from)
        self._straggler_clause = (self.hier.spec if self.hier is not None
                                  else straggler_scheduler)
        self.membership_events: list = []
        self.requeue_audits: list = []
        self._kill_hosts = (tuple(int(h) for h in kill_hosts)
                            if kill_hosts else None)
        self._kill_at = kill_at_step
        if self._kill_hosts is not None and not self.elastic:
            raise ValueError("kill_hosts injection requires elastic=True "
                             "(--elastic)")
        self._pending_unsplit = None
        self._churn_shares: Optional[np.ndarray] = None
        self.step_log: list = []    # per-step {step, dt_s, tokens, hosts}

        if cfg.name.startswith("minicpm"):
            sched_fn = wsd_schedule(lr, 20, 10_000, 1_000)   # the WSD paper
        else:
            sched_fn = cosine_schedule(lr, 20, 10_000)
        opt_init, opt_update = make_optimizer(cfg.optimizer, sched_fn)

        key = jax.random.PRNGKey(seed)
        with self.mesh, axis_rules(self.mesh, self.rules):
            # parameters and optimizer state are built directly in their
            # shardings: no device ever holds the whole unsharded state
            shapes, specs = self.model.init(key, jnp.bfloat16, abstract=True)
            pshard = shardings_for(specs, self.rules, self.mesh, tree=shapes)
            params = jax.jit(lambda k: self.model.init(k, jnp.bfloat16)[0],
                             out_shardings=pshard)(key)
            oshard = shardings_for(
                opt_state_specs(cfg.optimizer, shapes, specs),
                self.rules, self.mesh, tree=jax.eval_shape(opt_init, shapes))
            opt_state = jax.jit(opt_init, out_shardings=oshard)(params)
        self.params, self.opt_state = params, opt_state
        self.pshard, self.oshard = pshard, oshard
        self.specs = specs

        if self.fused_microbatches:
            step_fn = make_fused_train_step(self.model, opt_update,
                                            num_microbatches=num_microbatches)
        else:
            step_fn = make_train_step(self.model, opt_update,
                                      num_microbatches=num_microbatches)
        self._step = jax.jit(step_fn, donate_argnums=(0, 1))
        self._perm: Optional[jax.Array] = None
        self.step = 0
        self.corpus = SyntheticCorpus(cfg.vocab_size, mean_len=seq_len / 4,
                                      sigma=data_sigma, seed=seed)
        self._doc_iter = self.corpus.documents()
        # ``straggler_scheduler`` is a schedule clause like every other
        # surface; it turns the mitigator's AWF weights into integer token
        # shares.  min_host_share floors every host at 10% of the even
        # share so a throttled host keeps reporting (and can rehabilitate).
        self.mitigator = StragglerMitigator(num_hosts=hosts,
                                            scheduler=self._straggler_clause,
                                            min_share=min_host_share)
        # per-host input placement (batch rows block-split over "host")
        self._in_shard = None if hosts == 1 else "pending"
        self.last_shares: Optional[np.ndarray] = None
        self._host_tokens: Optional[np.ndarray] = None
        self.ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        self.ckpt_dir = ckpt_dir

    # ------------------------------------------------------------------
    def next_batch(self) -> Dict[str, jax.Array]:
        docs = [next(self._doc_iter) for _ in range(self.batch * 3)]
        packed = pack_with_scheduler(self.pack_sched, docs, self.batch,
                                     self.seq_len, history=self.history)
        batch = {"tokens": jnp.asarray(packed.tokens),
                 "labels": jnp.asarray(packed.labels),
                 "segment_ids": jnp.asarray(packed.segment_ids)}
        costs = ((packed.segment_ids > 0).sum(axis=1).astype(float)
                 if self.num_microbatches > 1 else None)
        if self.num_microbatches > 1 and self.hosts == 1:
            if self.fused_microbatches:
                # plan host-side (the UDS still decides the assignment),
                # but only ship the permutation — the gather itself runs
                # inside the fused jitted step, not as an eager dispatch
                perm = plan_microbatch_permutation(
                    self.microbatch_sched, costs, self.num_microbatches)
                self._perm = jnp.asarray(perm)
            else:
                batch = plan_microbatches(batch, costs,
                                          self.num_microbatches,
                                          scheduler=self.microbatch_sched)
        if self.capacity is not None:
            batch["cap_e"] = jnp.asarray(self.capacity.plan())
        if self.cfg.frontend != "none":
            # stub frontend: embed tokens host-side stand-in
            emb = jax.random.normal(
                jax.random.PRNGKey(self.step),
                (self.batch, self.seq_len, self.cfg.d_model), jnp.bfloat16)
            batch["embeds"] = emb
        if self.cfg.mrope_sections is not None:
            pos = jnp.tile(jnp.arange(self.seq_len, dtype=jnp.int32)[None],
                           (self.batch, 1))
            batch["positions_3d"] = jnp.stack([pos, pos, pos])
        if self.hosts > 1:
            # the UNSPLIT batch + host-side labels are held until the next
            # step completes: a membership change mid-step re-splits this
            # exact batch over the survivors (no step dropped at churn)
            if self.elastic:
                self._pending_unsplit = (dict(batch), packed.labels, costs)
            # plan: AWF token shares from the measured per-host rates (the
            # engine's plan cache makes this ~µs in steady state; each
            # observe_step's flush bumps the measured epoch, so changed
            # rates miss the cache and the shares REPLAN) -> uneven split.
            # The packer's numpy labels let the splitter count per-host
            # real tokens without a device round-trip.  Splitting happens
            # BEFORE any microbatch permutation: shares and attribution
            # are defined over the ORIGINAL contiguous host blocks.
            shares = self.mitigator.token_shares(self.batch * self.seq_len)
            batch, self._host_tokens = split_batch_by_shares(
                batch, shares, self.hosts, labels_np=packed.labels)
            self.last_shares = shares
            if self.num_microbatches > 1:
                # hier path (flat clauses were refused in __init__): the
                # device level permutes each host's block independently,
                # interleaved so microbatch m's host-h shard holds only
                # host-h rows — block ownership survives the reshape
                perm = plan_hier_microbatch_permutation(
                    self.microbatch_sched, costs, self.num_microbatches,
                    self.hosts, history=self.history)
                if self.fused_microbatches:
                    self._perm = jnp.asarray(perm)
                else:
                    batch = apply_microbatch_plan(batch, perm)
        return batch

    # ------------------------------------------------------- membership
    def apply_membership(self, lost: Sequence[int]) -> MembershipEvent:
        """Worker loss as a replan event: rebuild the spine for the
        survivors (requires ``elastic=True``).

        The full plan → execute → measure → replan treatment of a kill:

        1. **requeue** — if a scheduler-produced share plan was live, the
           dead hosts' token budgets are recovered from its chunk→worker
           provenance and replanned over the surviving team
           (``PlanEngine.requeue_plan``); survivors keep their own
           budgets.  Otherwise (uniform shares) the resized mitigator's
           cold-start shares are exactly uniform over the survivors.
           Either way the post-churn shares sum to the full token budget
           — no tokens silently lost.
        2. **mesh** — ``plan_degraded_mesh`` picks the surviving shape
           (warning about any idled devices), params/optimizer state are
           re-sharded onto the new ``("host", "model")`` mesh, and the
           jitted step recompiles against the new input shardings.
        3. **measure/replan** — a :class:`MembershipEvent` sentinel bumps
           the ``train_step`` measured epoch (cached adaptive plans
           invalidate), the mitigator resizes (rate windows floor at the
           churn), and the schedule clauses re-resolve over the new team
           size, so ``auto`` reselects from post-churn telemetry.

        Survivors are renumbered densely ``0..new_hosts-1`` in old-id
        order; the held unsplit batch (if any) is re-split by
        ``_resplit_pending`` so the in-flight step runs on the survivors.
        """
        from repro.runtime.elastic import plan_degraded_mesh
        if not self.elastic:
            raise RuntimeError("membership change requires elastic=True "
                               "(--elastic)")
        lost = sorted({int(h) for h in lost})
        if not lost:
            raise ValueError("no hosts named in the membership change")
        bad = [h for h in lost if not 0 <= h < self.hosts]
        if bad:
            raise ValueError(f"lost hosts {bad} outside the current team "
                             f"0..{self.hosts - 1}")
        survivors = [h for h in range(self.hosts) if h not in lost]
        if not survivors:
            raise ValueError("cannot lose every host")
        old_hosts = self.hosts
        shape = plan_degraded_mesh(len(survivors) * self.model_par,
                                   self.model_par)
        new_hosts = shape[0]
        while new_hosts > 1 and (
                self.batch % new_hosts
                or (self.num_microbatches > 1
                    and (self.batch // new_hosts) % self.num_microbatches)):
            new_hosts //= 2      # keep batch AND per-host blocks divisible
        event = MembershipEvent(kind="loss", old_size=old_hosts,
                                new_size=new_hosts, lost=tuple(lost),
                                step=self.step)

        # -- 1. requeue the dead hosts' unfinished token budget ---------
        total = self.batch * self.seq_len
        self._churn_shares = None
        plan = self.mitigator.last_plan
        if (plan is not None and self.last_shares is not None
                and len(survivors) == new_hosts
                and np.array_equal(plan.worker_iters(), self.last_shares)):
            new_plan, iters = get_engine().requeue_plan(
                plan, self._straggler_clause, lost_workers=lost,
                num_workers=new_hosts, history=self.mitigator.history)
            carried = np.asarray([self.last_shares[s] for s in survivors],
                                 np.int64)
            shares = carried + new_plan.worker_iters()
            self.requeue_audits.append({
                "step": self.step, "lost": list(lost),
                "ranges": plan.unfinished_ranges(lost),
                "requeued_iters": int(len(iters)),
                "carried": carried.tolist(),
                "shares": shares.tolist(),
            })
            if int(shares.sum()) != total:
                raise AssertionError(
                    f"requeued shares {shares.tolist()} do not cover "
                    f"{total} tokens — membership requeue lost work")
            self._churn_shares = shares

        # -- 2. rebuild mesh + resharding for the survivors -------------
        self.mesh = make_host_mesh(new_hosts, self.model_par)
        self.rules = rules_for(self.cfg, self.mesh, "train", self.batch)
        with self.mesh, axis_rules(self.mesh, self.rules):
            pshard = shardings_for(self.specs, self.rules, self.mesh,
                                   tree=self.params)
            self.params = jax.device_put(self.params, pshard)
            oshard = shardings_for(
                opt_state_specs(self.cfg.optimizer, self.params, self.specs),
                self.rules, self.mesh, tree=self.opt_state)
            self.opt_state = jax.device_put(self.opt_state, oshard)
        self.pshard, self.oshard = pshard, oshard
        self.hosts = new_hosts
        self.host_skew = np.asarray(
            [self.host_skew[s] for s in survivors[:new_hosts]], float)
        self._in_shard = None if new_hosts == 1 else "pending"

        # -- 3. epoch bump + resize + re-resolve over the new team ------
        self.telemetry.record_membership(event)
        self.mitigator.resize(new_hosts, lost=lost, step=self.step)
        self.pack_sched = resolve(self._scheduler_clause)
        if self.hier is not None:
            self.hier = self.pack_sched
        self.membership_events.append(event)
        return event

    def _resplit_pending(self):
        """Re-split the held unsplit batch over the post-churn team: the
        in-flight step survives the kill instead of being dropped.  Uses
        the requeued shares when a plan was live (survivor budgets
        carried, dead budgets replanned), else the resized mitigator's
        cold-start shares (exactly uniform — the split is a no-op and
        every real token of the step survives verbatim)."""
        if self._pending_unsplit is None:
            raise RuntimeError("no pending batch to re-split")
        batch, labels_np, costs = self._pending_unsplit
        if self.hosts == 1:
            self._host_tokens = np.asarray([(labels_np >= 0).sum()],
                                           np.int64)
            self.last_shares = np.asarray([self.batch * self.seq_len],
                                          np.int64)
            return self._replan_microbatches(batch, costs)
        shares = self._churn_shares
        if shares is None:
            shares = self.mitigator.token_shares(self.batch * self.seq_len)
        self._churn_shares = None
        batch, self._host_tokens = split_batch_by_shares(
            batch, shares, self.hosts, labels_np=labels_np)
        self.last_shares = shares
        return self._replan_microbatches(batch, costs)

    def _replan_microbatches(self, batch, costs):
        """Re-plan the microbatch permutation for the post-churn team: the
        held batch was stored UNPERMUTED, and the block-aligned interleave
        geometry depends on the (now changed) host count."""
        if self.num_microbatches <= 1 or costs is None:
            return batch
        if self.hosts > 1:
            perm = plan_hier_microbatch_permutation(
                self.microbatch_sched, costs, self.num_microbatches,
                self.hosts, history=self.history)
        else:
            perm = plan_microbatch_permutation(
                self.microbatch_sched, costs, self.num_microbatches,
                history=self.history)
        if self.fused_microbatches:
            self._perm = jnp.asarray(perm)
        else:
            batch = apply_microbatch_plan(batch, perm)
        return batch

    def _observe_multihost(self, dt: float) -> None:
        """The multi-host measure stage for one step: split the step's
        wall time over per-host ledgers (attribution weights = real token
        count x injected skew — see ``host_skew``), flush (one measured
        epoch), and feed the same per-host times to the mitigator whose
        AWF weights drive the next split."""
        ht = self._host_tokens
        w = ht.astype(float) * self.host_skew
        if w.sum() <= 0:
            w = np.ones(self.hosts)
        # each step is its own invocation (record() otherwise appends to
        # the last one forever and the measured epoch never advances)
        self.history.open_invocation("train_step")
        # one ledger per host over the step's global token index space
        off = 0
        for h in range(self.hosts):
            size = max(int(ht[h]), 1)
            self.telemetry.begin(h, Chunk(off, off + size, h))
            off += size
        self.telemetry.add_time_weighted(
            dt, {h: w[h] for h in range(self.hosts)},
            tokens={h: int(ht[h]) for h in range(self.hosts)})
        self.telemetry.flush()
        host_times = {h: dt * w[h] / w.sum() for h in range(self.hosts)}
        self.mitigator.observe_step(
            host_times, host_tokens={h: max(int(ht[h]), 1)
                                     for h in range(self.hosts)})

    def run(self, steps: int, log_every: int = 10) -> list:
        """One mesh context per STEP (not per run): a membership change
        mid-run swaps ``self.mesh`` for the survivors' mesh, and the next
        step must enter the new one."""
        losses = []
        for _ in range(steps):
            batch = self.next_batch()
            if (self._kill_at is not None and self._kill_hosts is not None
                    and self.step == self._kill_at):
                # injected kill between batch planning and execution — the
                # worst moment: the step's batch is already split for a
                # team that no longer exists.  Replan + re-split; the step
                # still runs (on the survivors), so no step is lost.
                self._kill_at = None
                self.apply_membership(self._kill_hosts)
                batch = self._resplit_pending()
            with self.mesh, axis_rules(self.mesh, self.rules):
                if self.hosts > 1:
                    if self._in_shard == "pending":
                        self._in_shard = batch_shardings(self.mesh,
                                                         self.rules, batch)
                    batch = jax.device_put(batch, self._in_shard)
                t0 = time.perf_counter()
                if self.fused_microbatches:
                    self.params, self.opt_state, metrics = self._step(
                        self.params, self.opt_state,
                        jnp.asarray(self.step, jnp.int32), batch, self._perm)
                else:
                    self.params, self.opt_state, metrics = self._step(
                        self.params, self.opt_state,
                        jnp.asarray(self.step, jnp.int32), batch)
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                tokens = int(metrics.get("tokens", self.batch * self.seq_len))
                if self.hosts > 1:
                    self._observe_multihost(dt)
                else:
                    # measure: one record per step (host 0, size = tokens),
                    # in its own invocation flushed immediately, so each
                    # step is one measured epoch
                    self.history.open_invocation("train_step")
                    self.telemetry.record_chunk(0, 0, max(tokens, 1), dt,
                                                tokens=tokens)
                    self.telemetry.flush()
                    self.mitigator.observe_step(
                        {0: dt}, host_tokens={0: max(tokens, 1)})
            self._pending_unsplit = None    # step survived; drop the hold
            losses.append(loss)
            self.step_log.append({"step": self.step, "dt_s": dt,
                                  "tokens": tokens, "hosts": self.hosts})
            self.step += 1
            if self.ckpt and self.step % 10 == 0:
                self.ckpt.save(self.step, {"params": self.params,
                                           "opt": self.opt_state})
            if self.step % log_every == 0:
                print(f"step {self.step:5d} loss {loss:.4f} "
                      f"({dt*1e3:.0f} ms, {tokens/max(dt,1e-9):.0f} "
                      f"tok/s)", flush=True)
        if self.ckpt:
            self.ckpt.wait()
        return losses


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--scheduler", default="fac2",
                    help='schedule clause: "fac2", "guided,4", '
                         '"uds:name(args)", "runtime" (late-bound from '
                         '$REPRO_SCHEDULE), "auto" (selected online from '
                         'telemetry), or a hierarchical composition '
                         '"hier(host=awf, device=guided,4)" whose host '
                         "level drives packing + token shares and whose "
                         "device level assigns microbatch rows per host "
                         "block (see docs/SCHEDULING.md)")
    ap.add_argument("--microbatch-scheduler", default="dynamic,1",
                    help="schedule clause for the microbatch assignment")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--fused-microbatches", action="store_true",
                    help="apply the UDS microbatch permutation on device "
                         "inside the jitted step (one dispatch per "
                         "optimizer step; numerically identical)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="data-parallel hosts; the AWF straggler loop "
                         "re-splits the batch unevenly across them "
                         "(emulate N on CPU: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--straggler-scheduler", default="wf2",
                    help="schedule clause turning AWF host weights into "
                         'token shares (any weight-aware clause, or "auto" '
                         "to select one online from step telemetry)")
    ap.add_argument("--min-host-share", type=float, default=0.1,
                    help="per-host floor as a fraction of the even share "
                         "(0 = let a straggler starve, 1 = pin static "
                         "even shares)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--elastic", action="store_true",
                    help="treat worker loss as a replan event: on a "
                         "membership change the loop rebuilds the mesh "
                         "for the survivors, requeues the dead hosts' "
                         "token budgets from plan provenance, and "
                         "re-resolves the schedule clauses over the new "
                         "team (see docs/SCHEDULING.md, Elastic "
                         "scheduling)")
    ap.add_argument("--kill-hosts", default=None,
                    help='injected-kill hook: comma-separated host ids to '
                         'lose at --kill-at (e.g. "2,3"); requires '
                         "--elastic")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="step index at which the injected kill fires "
                         "(between batch planning and execution)")
    args = ap.parse_args()

    enable_compile_cache()
    kill_hosts = ([int(h) for h in args.kill_hosts.split(",")]
                  if args.kill_hosts else None)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    loop = TrainLoop(cfg, batch=args.batch, seq_len=args.seq_len,
                     scheduler=args.scheduler,
                     microbatch_scheduler=args.microbatch_scheduler,
                     num_microbatches=args.microbatches,
                     fused_microbatches=args.fused_microbatches, lr=args.lr,
                     ckpt_dir=args.ckpt_dir, hosts=args.hosts,
                     straggler_scheduler=args.straggler_scheduler,
                     min_host_share=args.min_host_share,
                     elastic=args.elastic, kill_hosts=kill_hosts,
                     kill_at_step=args.kill_at)
    losses = loop.run(args.steps)
    if args.hosts > 1 and loop.last_shares is not None:
        frac = loop.last_shares / max(int(loop.last_shares.sum()), 1)
        print(f"host token shares: {np.round(frac, 3).tolist()} "
              f"(measured epoch {loop.mitigator.epoch()})")
    for ev in loop.membership_events:
        print(f"membership: {ev.kind} at step {ev.step} — "
              f"{ev.old_size} -> {ev.new_size} hosts (lost "
              f"{list(ev.lost)}); no step dropped, batch re-split over "
              f"the survivors")
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()
