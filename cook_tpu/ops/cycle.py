"""One full scheduling cycle as a single fused device program.

Composes the rank cycle and the match cycle the way the reference's
leader loop does (scheduler.clj:940-1036 match loop consuming the
rank loop's pool-name->pending-jobs-atom, :1281-1458):

  1. rank: DRU-score the union of running tasks and pending jobs
     (pending jobs are scored as hypothetical next tasks of their user,
     exactly how sort-jobs-by-dru-pool feeds both sets to
     dru/sorted-task-scored-task-pairs, scheduler.clj:1335-1376),
  2. considerable filter: walk pending jobs in fair-queue order and keep
     those whose user stays under their resource/count quota given
     running usage plus the queue prefix ahead of them
     (pending-jobs->considerable-jobs scheduler.clj:627-657,
     filter-based-on-quota tools.clj:905), capped at `num_considerable`
     (fenzo-max-jobs-considered, config.clj:319),
  3. match: greedy bin-packing assignment of the considerable jobs onto
     hosts (ops/match.py).

Everything runs on device in one jit; the host only ships deltas of the
job/offer tensors and reads back the assignment vector. The steps run
under the named scopes ``rank``, ``filter``, ``head_scan`` and
``epilogue`` (compaction and the decision window), so a device trace
attributes each op to its step.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from cook_tpu.obs import decisions as why_codes
from cook_tpu.ops import dru as dru_ops
from cook_tpu.ops import match as match_ops
from cook_tpu.ops.segments import segment_cumsum


class CycleResult(NamedTuple):
    pending_dru: jnp.ndarray     # (P,) dru score of each pending job
    queue_rank: jnp.ndarray      # (P,) fair-queue position among pending
    considerable: jnp.ndarray    # (P,) bool — survived quota/cap filters
    job_host: jnp.ndarray        # (P,) assigned host or -1
    mem_left: jnp.ndarray        # (H,)
    cpus_left: jnp.ndarray       # (H,)
    gpus_left: jnp.ndarray       # (H,)
    slots_left: jnp.ndarray      # (H,) i32
    # compact views of the considerable batch, queue-ordered — the
    # device-resident coordinator reads ONLY these back (2xC i32 per
    # cycle instead of P-sized vectors):
    cons_idx: jnp.ndarray        # (C,) pending-row index per compact slot,
                                 # -1 = empty slot
    cons_host: jnp.ndarray       # (C,) assigned host per compact slot, -1
    head_matched: jnp.ndarray    # () bool — queue-head considerable placed
    n_matched: jnp.ndarray       # () i32
    n_considerable: jnp.ndarray  # () i32
    # compaction epilogue: the MATCHED slots packed to the front in
    # queue order (-1 pad). A consumer reads n_matched first and then
    # fetches only the prefix — 2 x n_matched i32 over the link instead
    # of 2 x C (and instead of the (P,)-sized job_host vector), which
    # is what bounds the sync readback over the host link.
    mat_idx: jnp.ndarray         # (C,) pending-row index, matched prefix
    mat_host: jnp.ndarray        # (C,) assigned host, matched prefix
    # decision provenance (obs/decisions.py codes): why each of the
    # first W = min(C, P) fair-queue positions did or didn't launch.
    # Queue-ordered and produced by the same epilogue pass, so the
    # consumer's existing readback picks them up with no extra
    # device->host sync; positions beyond W are answered host-side as
    # rank-beyond-window.
    why_idx: jnp.ndarray         # (W,) pending-row index at queue pos, -1
    why_code: jnp.ndarray        # (W,) i32 reason code (0 = pad)
    why_amt: jnp.ndarray         # (W,) f32 code-specific datum (host id,
                                 # rank ordinal, or quota overage)


@functools.partial(jax.jit, static_argnames=("num_considerable", "num_groups",
                                             "sequential", "use_pallas",
                                             "dru_mode", "match_kw",
                                             "matcher"))
def rank_and_match(
    # running tasks (R slots)
    run_user, run_mem, run_cpus, run_prio, run_start, run_valid,
    run_mem_share, run_cpus_share,
    # pending jobs (P slots)
    pend_user, pend_mem, pend_cpus, pend_gpus, pend_prio, pend_start,
    pend_valid, pend_mem_share, pend_cpus_share, pend_group,
    pend_unique_group,
    # hosts
    hosts: match_ops.Hosts,
    forbidden,                 # None | (P, H) bool dense | tuple of
                               # (rows (K, H) bool, slot_of (P,) i32) —
                               # the sparse resident form: row p's mask
                               # is rows[slot_of[p]] when slot_of[p] >= 0,
                               # all-allowed otherwise. K << P because
                               # only constrained jobs own a mask row.
    # per-user quotas (U,)
    user_quota_mem, user_quota_cpus, user_quota_count,
    num_considerable: int = 1024,
    num_groups: int = 1,
    sequential: bool = True,
    considerable_limit=None,
    bonus=None,                # data-locality fitness bonus: (P, H) f32
                               # dense, or tuple (rows (Kb, H) f32,
                               # slot_of (P,) i32) — the sparse resident
                               # form mirroring `forbidden`: row p's
                               # bonus is rows[slot_of[p]] when
                               # slot_of[p] >= 0, zero otherwise. Only
                               # jobs with datasets own a bonus row.
    use_pallas: bool = False,  # fused Pallas TPU kernel in match_rounds
    dru_mode: str = "default",  # "default" (cpu/mem) | "gpu" (pool
                                # dru-mode :pool.dru-mode/gpu, schema.clj:816)
    run_gpus=None,             # (R,) — required in gpu mode
    run_gpu_share=None,        # (R,) — required in gpu mode
    pend_gpu_share=None,       # (P,) — required in gpu mode
    match_kw=None,             # extra match_rounds knobs (head_exact,
                               # dense_rounds, rounds...) for per-config
                               # tuning; ignored on the sequential path.
                               # STATIC under jit: pass a hashable
                               # (tuple of (name, value) pairs)
    pend_ports=None,           # (P,) i32 requested port count; with
    host_ports=None,           # (H,) i32 free ports — folds the ports
                               # feasibility check (task.clj:254-280)
                               # into the compact forbidden mask
    pend_est_s=None,           # (P,) i32 capped expected-runtime seconds
    host_death_s=None,         # (H,) i32 host death time (s, relative
                               # epoch; sentinel = no advertised start).
    now_s=None,                # () i32 wall clock on the same epoch —
                               # with pend_est_s/host_death_s this folds
                               # the estimated-completion constraint
                               # (constraints.clj:200-247) into the
                               # compact mask as a pure time-lane
                               # comparison, so host lifetimes decay on
                               # device without any per-cycle re-masking
    matcher=None,              # match-step override: callable
                               # (jobs, hosts, forb, bonus)->MatchResult.
                               # STATIC under jit (keep the callable's
                               # identity stable across cycles). The
                               # host-sharded resident pool passes the
                               # mesh-bound distributed scan here
                               # (parallel/sharded_match.resident_matcher)
) -> CycleResult:
    R = run_user.shape[0]
    P = pend_user.shape[0]
    U = user_quota_mem.shape[0]

    # ---- 1. rank union of running + pending --------------------------
    with jax.named_scope("rank"):
        user = jnp.concatenate([run_user, pend_user])
        prio = jnp.concatenate([run_prio, pend_prio])
        start = jnp.concatenate([run_start, pend_start])
        valid = jnp.concatenate([run_valid, pend_valid])

        if dru_mode == "gpu":
            gpus = jnp.concatenate([run_gpus, pend_gpus])
            gshare = jnp.concatenate([run_gpu_share, pend_gpu_share])
            ranked = dru_ops.gpu_dru_rank(user, gpus, prio, start, valid,
                                          gshare)
        else:
            mem = jnp.concatenate([run_mem, pend_mem])
            cpus = jnp.concatenate([run_cpus, pend_cpus])
            mshare = jnp.concatenate([run_mem_share, pend_mem_share])
            cshare = jnp.concatenate([run_cpus_share, pend_cpus_share])
            ranked = dru_ops.dru_rank(user, mem, cpus, prio, start, valid,
                                      mshare, cshare)
        pending_dru = ranked.dru[R:]
        # fair-queue position among *pending* jobs only: order pending by
        # their global rank.
        pend_global_rank = ranked.rank[R:]
        queue_perm = jnp.argsort(
            jnp.where(pend_valid, pend_global_rank, jnp.iinfo(jnp.int32).max))
        queue_rank = jnp.zeros(P, jnp.int32).at[queue_perm].set(
            jnp.arange(P, dtype=jnp.int32))

    # ---- 2. considerable filter (quota + cap) ------------------------
    with jax.named_scope("filter"):
        # running usage per user
        def usage(vals):
            return jax.ops.segment_sum(jnp.where(run_valid, vals, 0.0),
                                       jnp.where(run_valid, run_user, U),
                                       num_segments=U + 1)[:U]

        u_mem = usage(run_mem)
        u_cpus = usage(run_cpus)
        u_cnt = jax.ops.segment_sum(run_valid.astype(jnp.float32),
                                    jnp.where(run_valid, run_user, U),
                                    num_segments=U + 1)[:U]

        # cumulative pending demand per user in queue order
        q_user = pend_user[queue_perm]
        q_valid = pend_valid[queue_perm]
        sort_user = jnp.where(q_valid, q_user, U)
        uperm = jnp.argsort(sort_user, stable=True)
        su = sort_user[uperm]
        cum = segment_cumsum(
            jnp.stack([jnp.where(q_valid, pend_mem[queue_perm], 0.0)[uperm],
                       jnp.where(q_valid, pend_cpus[queue_perm], 0.0)[uperm],
                       q_valid[uperm].astype(jnp.float32)], -1), su)
        uid = jnp.clip(su, 0, U - 1)
        # signed per-dimension overage (positive = this dim would exceed the
        # user's quota): the quota gate AND the provenance datum in one pass
        over = jnp.stack(
            [u_mem[uid] + cum[:, 0] - user_quota_mem[uid],
             u_cpus[uid] + cum[:, 1] - user_quota_cpus[uid],
             u_cnt[uid] + cum[:, 2] - user_quota_count[uid]], -1)
        within = (over[:, 0] <= 0) & (over[:, 1] <= 0) & (over[:, 2] <= 0)
        within_q = jnp.zeros(P, bool).at[uperm].set(within)      # queue order
        over_q = jnp.zeros((P, 3)).at[uperm].set(over)           # queue order
        considerable_q = q_valid & within_q
        # cap at num_considerable (static, sets the compact batch shape) and
        # at considerable_limit (dynamic, the scaleback feedback value —
        # scheduler.clj:1002-1036 — which must not trigger a recompile)
        cap = num_considerable if considerable_limit is None else \
            jnp.minimum(jnp.int32(num_considerable),
                        jnp.asarray(considerable_limit, jnp.int32))
        taken = jnp.cumsum(considerable_q.astype(jnp.int32))
        considerable_q &= taken <= cap
        considerable = jnp.zeros(P, bool).at[queue_perm].set(considerable_q)

        # ---- 3. compact the considerable head, then match ----------------
    #         (the compaction is the filter's output; the match is the
    #         head scan)
        # Gather the first num_considerable queue entries into a dense C-batch
        # so the match kernel's (jobs x hosts) working set is C x H, not P x H
        # (at 100k pending x 10k offers a dense P x H mask would be ~1 GB).
        C = num_considerable
        H = hosts.mem.shape[0]
        cons_pos = jnp.cumsum(considerable_q.astype(jnp.int32)) - 1
        slot = jnp.where(considerable_q, jnp.minimum(cons_pos, C), C)
        # src[c] = queue position feeding compact slot c (P = empty slot)
        src = jnp.full(C + 1, P, jnp.int32).at[slot].set(
            jnp.arange(P, dtype=jnp.int32), mode="drop")[:C]
        in_use = src < P
        srcc = jnp.clip(src, 0, P - 1)
        # compose queue_perm with the compact slots once, so each gather below
        # is a direct (C,)-sized gather — never a (P, H) intermediate
        pend_idx = queue_perm[srcc]

        def gq(arr):  # gather: original pending order -> compact batch
            return arr[pend_idx]

        jobs = match_ops.Jobs(
            mem=gq(pend_mem), cpus=gq(pend_cpus), gpus=gq(pend_gpus),
            valid=in_use,
            group=gq(pend_group), unique_group=gq(pend_unique_group),
        )
        if forbidden is None:
            forb = match_ops.varying_full(hosts.valid, False, (C, H), bool)
        elif isinstance(forbidden, tuple):
            rows, slot_of = forbidden
            Kc = rows.shape[0]
            slot = slot_of[pend_idx]
            forb = jnp.where((slot >= 0)[:, None],
                             rows[jnp.clip(slot, 0, Kc - 1)], False)
            forb &= in_use[:, None]
        else:
            forb = forbidden[pend_idx] & in_use[:, None]
        if pend_ports is not None and host_ports is not None:
            forb = forb | (pend_ports[pend_idx][:, None] > host_ports[None, :])
        if pend_est_s is not None and host_death_s is not None:
            # est_end >= death forbids the host; est <= 0 = unconstrained
            est = pend_est_s[pend_idx]
            forb = forb | ((est > 0)[:, None]
                           & ((now_s + est)[:, None] >= host_death_s[None, :]))
        if bonus is None:
            bonusc = None
        elif isinstance(bonus, tuple):
            brows, bslot = bonus
            Kb = brows.shape[0]
            bs = bslot[pend_idx]
            bonusc = jnp.where((bs >= 0)[:, None],
                               brows[jnp.clip(bs, 0, Kb - 1)], 0.0) \
                * in_use[:, None]
        else:
            bonusc = bonus[pend_idx] * in_use[:, None]
    with jax.named_scope("head_scan"):
        if matcher is not None:
            res = matcher(jobs, hosts, forb, bonusc)
        elif sequential:
            res = match_ops.match_scan(
                jobs, hosts, forb, num_groups=num_groups, bonus=bonusc,
                use_pallas=use_pallas and bonus is None)
        else:
            kw = {"rounds": 4, **dict(match_kw or ())}
            res = match_ops.match_rounds(jobs, hosts, forb,
                                         num_groups=num_groups, bonus=bonusc,
                                         use_pallas=use_pallas, **kw)
    with jax.named_scope("epilogue"):
        # scatter back: compact -> original pending order in one scatter
        # (empty compact slots get index P and are dropped)
        scatter_idx = jnp.where(in_use, pend_idx, P)
        job_host = jnp.full(P, match_ops.NO_HOST).at[scatter_idx].set(
            res.job_host, mode="drop")

        # compact outputs: slot order IS queue order (slots were assigned by
        # queue-position cumsum), so the launch loop walks cons_idx directly
        cons_idx = jnp.where(in_use, pend_idx, -1).astype(jnp.int32)
        matched_slot = in_use & (res.job_host >= 0)
        head_matched = ~in_use[0] | (res.job_host[0] >= 0)
        # compaction epilogue: pack the matched slots to the front with the
        # same cumsum-position scatter used for the considerable batch above
        # (slots are queue-ordered and the cumsum is monotone, so the prefix
        # stays in queue order — the launch loop's walk order is unchanged)
        mat_pos = jnp.cumsum(matched_slot.astype(jnp.int32)) - 1
        mslot = jnp.where(matched_slot, jnp.minimum(mat_pos, C), C)
        mat_idx = jnp.full(C + 1, -1, jnp.int32).at[mslot].set(
            cons_idx, mode="drop")[:C]
        mat_host = jnp.full(C + 1, -1, jnp.int32).at[mslot].set(
            res.job_host.astype(jnp.int32), mode="drop")[:C]

        # ---- 4. decision provenance --------------------------------------
        # Reason code per fair-queue position over the window W = min(C, P)
        # (static: queue-order vectors are (P,), the compact batch is (C,)).
        # Every input below already exists in queue order — this is pure
        # epilogue arithmetic, no new gathers over (P, H).
        W = min(C, P)
        wqp = queue_perm[:W]
        wvalid = q_valid[:W]
        whost = job_host[wqp]                 # host the position matched, -1
        wcons = considerable_q[:W]            # survived quota AND cap
        wwithin = within_q[:W]
        wtaken = taken[:W]                    # pre-cap considerable ordinal
        wover = over_q[:W]
        # first-failing quota dimension, mem -> cpus -> count priority
        quota_code = jnp.where(
            wover[:, 0] > 0, why_codes.QUOTA_MEM,
            jnp.where(wover[:, 1] > 0, why_codes.QUOTA_CPUS,
                      why_codes.QUOTA_COUNT))
        quota_amt = jnp.where(
            wover[:, 0] > 0, wover[:, 0],
            jnp.where(wover[:, 1] > 0, wover[:, 1], wover[:, 2]))
        why_code = jnp.where(
            ~wvalid, why_codes.INVALID,
            jnp.where(wcons,
                      jnp.where(whost >= 0, why_codes.MATCHED,
                                why_codes.NO_HOST_FIT),
                      jnp.where(~wwithin, quota_code,
                                why_codes.RANK_CUTOFF))).astype(jnp.int32)
        why_amt = jnp.where(
            ~wvalid, 0.0,
            jnp.where(wcons, jnp.where(whost >= 0, whost.astype(jnp.float32),
                                       0.0),
                      jnp.where(~wwithin, quota_amt,
                                wtaken.astype(jnp.float32))))
        why_idx = jnp.where(wvalid, wqp, -1).astype(jnp.int32)

        return CycleResult(pending_dru=pending_dru, queue_rank=queue_rank,
                           considerable=considerable, job_host=job_host,
                           mem_left=res.mem_left, cpus_left=res.cpus_left,
                           gpus_left=res.gpus_left, slots_left=res.slots_left,
                           cons_idx=cons_idx, cons_host=res.job_host,
                           head_matched=head_matched,
                           n_matched=matched_slot.sum().astype(jnp.int32),
                           n_considerable=in_use.sum().astype(jnp.int32),
                           mat_idx=mat_idx, mat_host=mat_host,
                           why_idx=why_idx, why_code=why_code,
                           why_amt=why_amt)
