"""Auto-parallel cost model + parallel-strategy tuner.

Parity: `python/paddle/distributed/auto_parallel/cost_model.py` (comp/comm
cost graph simulation) and `auto_parallel/tuner/` (parallel-strategy
search). TPU-native re-design: instead of simulating a serialized Program
op-graph, the model prices a transformer-family training step analytically
from the hardware roofline —

  comp  = step FLOPs / (MXU peak x efficiency), stretched by the ACTUAL
          schedule's bubble fraction (tick decode via
          `pipeline_schedule.schedule_bubble_ticks`, so gpipe / 1f1b /
          zero_bubble price differently; zero_bubble additionally pays
          its extra forward recompute)
  comm  = bytes moved per collective / ICI bandwidth (ring allreduce =
          2 (n-1)/n x bytes, all_gather/reduce_scatter = (n-1)/n x bytes)
          + a per-collective dispatch latency, so the dp grad sync is
          priced per BUCKET: bucket_size=0 models the per-parameter
          eager path (n_param_tensors collectives), bucket_size>0 models
          the fused path, whose reductions overlap the backward except
          for the tail bucket
  mem   = params + grads + optimizer state (/ zero shard factor)
          + activations (/ pp mp, x remat factor; zero_bubble holds its
          O(M) act+cotangent stashes); configs over the HBM budget are
          infeasible

and the tuner brute-force scores every (dp, mp, pp, zero, micro,
schedule, bucket_size) mesh factorization — the search space is tiny
(divisors of n_devices x a few schedules/buckets), so beam search is
unnecessary on TPU pods.

`tune()` is the measurement-driven entry (the "Integrated Hardware
Architecture and Device Placement Search" direction, PAPERS.md): feed it
a short profiled run's numbers (PR 1 metrics registry: step seconds or
measured MFU, eager collective bytes/seconds) and it calibrates the
cluster's `mxu_efficiency` / `ici_bw` terms before searching, then
reports the chosen config WITH its predicted MFU so the prediction can
be checked against the next measurement.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional


@dataclasses.dataclass
class ClusterSpec:
    """One TPU slice. Defaults are v5e-ish."""
    n_devices: int = 8
    peak_flops: float = 197e12       # bf16 per chip
    hbm_bytes: float = 16e9
    ici_bw: float = 9e10             # bytes/s per direction per link
    dcn_bw: float = 2.5e10
    mxu_efficiency: float = 0.4      # achievable fraction of peak
    collective_latency: float = 2e-5  # dispatch+setup per collective


@dataclasses.dataclass
class ModelSpec:
    """Transformer-family training job description."""
    n_layers: int
    d_model: int
    seq_len: int
    vocab_size: int
    d_ff: int = 0
    global_batch: int = 32
    n_heads: int = 0                 # 0 = no head-divisibility constraint
    param_bytes: int = 2             # bf16 params
    grad_bytes: int = 4
    opt_state_bytes: int = 8         # Adam m+v fp32... per param elem
    master_bytes: int = 4            # fp32 master copy
    act_bytes: int = 2
    remat: bool = True
    # MoE (ISSUE 10): E experts replace the dense FFN; each token
    # computes top_k of them, the fixed [E, C, d] dispatch buffers pad
    # compute up to capacity_factor, and the ep mesh axis shards the
    # expert params + rides the dispatch/combine all_to_all
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25

    def __post_init__(self):
        if self.d_ff == 0:
            self.d_ff = 4 * self.d_model

    @property
    def expert_param_elems(self) -> int:
        """Parameter elements sharded over the ep axis (the stacked
        expert FFNs); 0 for dense models."""
        if not self.moe_experts:
            return 0
        return 2 * self.d_model * self.d_ff * self.moe_experts \
            * self.n_layers

    @property
    def n_params(self) -> int:
        d, L = self.d_model, self.n_layers
        shared = 4 * d * d * L + self.vocab_size * d + self.seq_len * d
        if self.moe_experts:
            return shared + d * self.moe_experts * L \
                + self.expert_param_elems
        return shared + 2 * d * self.d_ff * L

    @property
    def active_params(self) -> int:
        """Parameters each token actually multiplies (the MFU
        numerator base): top_k experts for MoE, everything for
        dense."""
        if not self.moe_experts:
            return self.n_params
        d, L = self.d_model, self.n_layers
        return (4 * d * d + d * self.moe_experts
                + self.moe_top_k * 2 * d * self.d_ff) * L \
            + self.vocab_size * d + self.seq_len * d

    @property
    def n_param_tensors(self) -> int:
        """Parameter-tensor count estimate (12 per block + embeddings/
        final LN/head): the collective count of an UNbucketed
        per-parameter grad reduction."""
        return (13 if self.moe_experts else 12) * self.n_layers + 4

    def step_flops(self) -> float:
        """fwd+bwd (+recompute) matmul FLOPs for one global batch —
        the COMPUTED flops: MoE pays for every capacity slot (E * C =
        ~capacity_factor * top_k * T), not just the routed tokens."""
        toks = self.global_batch * self.seq_len
        base = self.useful_flops()
        if self.moe_experts:
            # E * C slots are computed vs top_k routed per token:
            # (cap_factor - 1) * top_k extra slot-equivalents each
            pad = max((self.moe_capacity_factor - 1.0)
                      * self.moe_top_k, 0.0)
            base += 6.0 * 2 * self.d_model * self.d_ff \
                * self.n_layers * pad * toks
        if self.remat:
            base *= 4.0 / 3.0  # one extra forward
        return base

    def useful_flops(self) -> float:
        """Model FLOPs for one global batch WITHOUT recompute or
        capacity-padding overhead — the MFU numerator (same
        6N_active + 6*L*S*d per-token convention as the train cell's
        MFU line, `benchmarks/harness/stats.py`)."""
        toks = self.global_batch * self.seq_len
        return (6.0 * self.active_params
                + 6.0 * self.n_layers * self.seq_len * self.d_model) \
            * toks


@dataclasses.dataclass
class Strategy:
    dp: int = 1
    mp: int = 1
    pp: int = 1
    ep: int = 1                      # expert parallel (MoE only)
    micro_batches: int = 1
    zero_stage: int = 0
    schedule: str = "1f1b"           # gpipe | 1f1b | zero_bubble
    virtual_stages: int = 1
    bucket_size: int = 0             # 0 = per-parameter grad reduction

    def degree(self):
        return self.dp * self.mp * self.pp * self.ep

    def as_hybrid_configs(self):
        return {"dp_degree": self.dp, "mp_degree": self.mp,
                "pp_degree": self.pp, "ep_degree": self.ep,
                "sharding_degree": 1,
                "micro_batches": self.micro_batches,
                "zero_stage": self.zero_stage,
                "schedule": self.schedule,
                "virtual_stages": self.virtual_stages,
                "bucket_size": self.bucket_size}


def _ring_allreduce_time(bytes_, n, bw):
    if n <= 1 or bytes_ <= 0:
        return 0.0
    return 2.0 * (n - 1) / n * bytes_ / bw


def _shard_xfer_time(bytes_, n, bw):
    """all_gather or reduce_scatter of a full buffer over n ranks."""
    if n <= 1 or bytes_ <= 0:
        return 0.0
    return (n - 1) / n * bytes_ / bw


# fraction of the compute step a bucketed+overlapped dp reduction can
# hide behind (the backward half of fwd+bwd issues buckets as layers
# retire); the tail bucket is always exposed
_OVERLAP_WINDOW = 0.5


class CostModel:
    """Analytic step-time + memory estimate for a (model, strategy) pair."""

    def __init__(self, cluster: Optional[ClusterSpec] = None):
        self.cluster = cluster or ClusterSpec()

    # -------------------------------------------------------------- mem
    def memory_per_device(self, m: ModelSpec, s: Strategy) -> float:
        # params + grads live sharded over mp and pp always; the
        # expert-stacked FFN params additionally shard over ep
        shard = s.mp * s.pp
        P_eff = float(m.n_params - m.expert_param_elems) \
            + float(m.expert_param_elems) / max(s.ep, 1)
        P = P_eff
        p_bytes = P * m.param_bytes / shard
        g_bytes = P * m.grad_bytes / shard
        # optimizer state (+master weights): zero>=1 additionally shards
        # over dp; zero>=2 shards grads; zero>=3 shards params too
        opt_shard = shard * (s.dp if s.zero_stage >= 1 else 1)
        o_bytes = P * (m.opt_state_bytes + m.master_bytes) / opt_shard
        if s.zero_stage >= 2:
            g_bytes /= s.dp
        if s.zero_stage >= 3:
            p_bytes /= s.dp  # params stored sharded between steps
        # activations: batch split over dp x ep, per-microbatch live set
        # over pp stages; remat keeps ~1 residual per layer boundary
        b_local = max(m.global_batch // (s.dp * s.ep
                                         * s.micro_batches), 1)
        act_per_layer = b_local * m.seq_len * m.d_model * m.act_bytes
        layers_local = max(m.n_layers // s.pp, 1)
        live_factor = 2.0 if m.remat else 14.0   # resid vs full act set
        # gpipe keeps micro_batches in flight; 1f1b keeps <= pp;
        # zero_bubble stashes EVERY micro's input AND cotangent until
        # its deferred W sub-tick (pipeline_schedule module doc)
        if s.pp > 1 and s.schedule == "zero_bubble":
            in_flight = 2 * s.micro_batches
        else:
            in_flight = min(s.micro_batches, s.pp)
        a_bytes = act_per_layer * layers_local * live_factor * in_flight \
            / max(s.mp, 1)
        return p_bytes + g_bytes + o_bytes + a_bytes

    # ------------------------------------------------------------- time
    def _bubble_stretch(self, s: Strategy) -> float:
        """Schedule-tick stretch T / active_ticks from the real decode
        formulas: the factor pure compute inflates by when the device
        idles in fill/drain slots."""
        if s.pp <= 1:
            return 1.0
        from .pipeline_schedule import schedule_bubble_ticks
        bubbles, T = schedule_bubble_ticks(
            s.schedule, s.pp, s.virtual_stages, s.micro_batches)
        active = T - bubbles[0]
        return T / max(active, 1)

    def comp_time(self, m: ModelSpec, s: Strategy,
                  efficiency: Optional[float] = None) -> float:
        c = self.cluster
        eff = c.mxu_efficiency if efficiency is None else efficiency
        flops = m.step_flops() / s.degree()
        if s.pp > 1 and s.schedule == "zero_bubble":
            # B and W each replay the stage forward from the stash: one
            # recompute more than the remat baseline
            flops *= (10.0 / 8.0) if m.remat else (8.0 / 6.0)
        return flops / (c.peak_flops * eff) * self._bubble_stretch(s)

    def comm_time(self, m: ModelSpec, s: Strategy) -> float:
        c = self.cluster
        # dp grad sync: allreduce (zero=0) or RS+AG (zero>=1) of the
        # mp/pp-local shard (the ep-sharded expert grads sync over dp
        # at 1/ep size each — same aggregate as dividing by ep here)
        P = float(m.n_params - m.expert_param_elems) \
            + float(m.expert_param_elems) / max(s.ep, 1)
        comm = 0.0
        g_local = P * m.grad_bytes / (s.mp * s.pp)
        if s.dp > 1:
            if s.zero_stage >= 1:
                comm += 2.0 * _shard_xfer_time(g_local, s.dp, c.ici_bw) \
                    + 2.0 * c.collective_latency
            elif s.bucket_size > 0:
                n_buckets = max(1, math.ceil(g_local / s.bucket_size))
                ring = _ring_allreduce_time(g_local, s.dp, c.ici_bw)
                tail = _ring_allreduce_time(
                    min(float(s.bucket_size), g_local), s.dp, c.ici_bw)
                hide = _OVERLAP_WINDOW * self.comp_time(m, s)
                comm += max(tail, ring - hide) \
                    + n_buckets * c.collective_latency
            else:
                comm += _ring_allreduce_time(g_local, s.dp, c.ici_bw) \
                    + m.n_param_tensors * c.collective_latency
        if s.zero_stage >= 3 and s.dp > 1:
            # params stored sharded: all-gather them for fwd AND for the
            # recomputing bwd
            p_local = P * m.param_bytes / (s.mp * s.pp)
            comm += 2.0 * _shard_xfer_time(p_local, s.dp, c.ici_bw)
        # mp: 2 allreduce fwd + 2 bwd per layer of [B_local, S, d] acts
        if s.mp > 1:
            b_local = max(m.global_batch // (s.dp * s.ep), 1)
            act = b_local * m.seq_len * m.d_model * m.act_bytes
            layers_local = max(m.n_layers // s.pp, 1)
            comm += 4.0 * layers_local * (_ring_allreduce_time(
                act, s.mp, c.ici_bw) + c.collective_latency)
        # ep: dispatch + combine all_to_all of the [E, C, d] capacity
        # buffers per layer, fwd + bwd (4 exchanges); an all_to_all
        # moves (ep-1)/ep of the payload off-chip
        if s.ep > 1 and m.moe_experts:
            toks_local = max(m.global_batch // (s.dp * s.ep), 1) \
                * m.seq_len
            slots = m.moe_capacity_factor * m.moe_top_k * toks_local
            a2a = slots * m.d_model * m.act_bytes * (s.ep - 1) / s.ep
            layers_local = max(m.n_layers // s.pp, 1)
            comm += 4.0 * layers_local * (a2a / c.ici_bw
                                          + c.collective_latency)
        # pp: p2p activation sends per microbatch tick (fwd+bwd)
        if s.pp > 1:
            b_micro = max(m.global_batch // (s.dp * s.ep
                                             * s.micro_batches), 1)
            act = b_micro * m.seq_len * m.d_model * m.act_bytes
            comm += 2.0 * s.micro_batches * act / c.ici_bw
        return comm

    def step_time(self, m: ModelSpec, s: Strategy) -> float:
        return self.comp_time(m, s) + self.comm_time(m, s)

    def predicted_mfu(self, m: ModelSpec, s: Strategy) -> float:
        """Useful-FLOPs MFU per chip at the predicted step time (same
        numerator convention as the train cell's measured MFU)."""
        t = self.step_time(m, s)
        return m.useful_flops() / (t * s.degree() * self.cluster.peak_flops)

    # ------------------------------------------------------ calibration
    def calibrate(self, m: ModelSpec, measurements: dict) -> ClusterSpec:
        """Fit cluster terms from a measured run (PR 1 metrics registry
        numbers) and return a NEW ClusterSpec.

        measurements keys:
          strategy           Strategy (or dict of its fields) the
                             measurement ran under; default Strategy()
          step_seconds       measured wall seconds per train step, OR
          mfu                measured useful-FLOPs MFU per chip
          collective_bytes   + collective_seconds: eager wire totals
                             (fits ici_bw = bytes/seconds)

        mxu_efficiency solves comp_time(eff) = t_meas - comm_pred (the
        comp term is linear in 1/eff); clamped to [0.02, 0.95].
        """
        strat = measurements.get("strategy") or Strategy()
        if isinstance(strat, dict):
            strat = Strategy(**{k: v for k, v in strat.items()
                                if k in {f.name for f in
                                         dataclasses.fields(Strategy)}})
        cluster = dataclasses.replace(self.cluster)
        cb = measurements.get("collective_bytes")
        cs = measurements.get("collective_seconds")
        if cb and cs:
            cluster.ici_bw = float(cb) / float(cs)
        cm = CostModel(cluster)
        t_meas = measurements.get("step_seconds")
        if t_meas is None and measurements.get("mfu"):
            t_meas = m.useful_flops() / (
                float(measurements["mfu"]) * strat.degree()
                * cluster.peak_flops)
        if t_meas:
            unit = cm.comp_time(m, strat, efficiency=1.0)
            comp_budget = float(t_meas) - cm.comm_time(m, strat)
            eff = unit / max(comp_budget, unit / 0.95)
            cluster.mxu_efficiency = min(max(eff, 0.02), 0.95)
        return cluster


class StrategyTuner:
    """Brute-force search over mesh factorizations (the reference tuner's
    role, minus the Program rewriting — shardings here are GSPMD specs)."""

    def __init__(self, cluster: Optional[ClusterSpec] = None):
        self.cluster = cluster or ClusterSpec()
        self.cost_model = CostModel(self.cluster)

    def _factorizations(self, n, with_ep=False):
        for dp in range(1, n + 1):
            if n % dp:
                continue
            rest = n // dp
            for mp in range(1, rest + 1):
                if rest % mp:
                    continue
                rest2 = rest // mp
                if not with_ep:
                    yield dp, mp, rest2, 1
                    continue
                for pp in range(1, rest2 + 1):
                    if rest2 % pp:
                        continue
                    yield dp, mp, pp, rest2 // pp

    def search(self, model: ModelSpec, n_devices: Optional[int] = None,
               top_k: int = 1, schedules=("1f1b",), bucket_sizes=(0,),
               zero_stages=(0, 1, 2, 3)):
        n = n_devices or self.cluster.n_devices
        moe = model.moe_experts > 0
        scored = []
        for dp, mp, pp, ep in self._factorizations(n, with_ep=moe):
            if model.n_layers % pp or model.global_batch % (dp * ep):
                continue
            if model.n_heads and (mp > model.n_heads
                                  or model.n_heads % mp):
                continue
            if model.vocab_size % mp:
                continue
            # ep must divide the expert count — an ep that strands a
            # fractional expert per rank is INFEASIBLE, not just slow
            if ep > 1 and (not moe or model.moe_experts % ep):
                continue
            micro_opts = {1} if pp == 1 else {
                mb for mb in (pp, 2 * pp, 4 * pp)
                if model.global_batch % (dp * ep * mb) == 0}
            sched_opts = schedules if pp > 1 else ("1f1b",)
            # bucketed grad reduction exists only on the pure DENSE-DP
            # executor path (hybrid_gpt's grad_bucket_bytes contract —
            # MoE expert leaves are ep-sharded, never plain-dp-psummed):
            # scoring buckets elsewhere would rank a config no executor
            # can run and let a near-tie flip the mesh choice
            buck_opts = bucket_sizes if (dp > 1 and mp == 1
                                         and pp == 1 and ep == 1
                                         and not moe) else (0,)
            for micro in sorted(micro_opts):
                for zero in zero_stages:
                    for sched in sched_opts:
                        for bucket in buck_opts:
                            if bucket and zero >= 1:
                                continue  # RS/AG path, nothing to bucket
                            s = Strategy(dp=dp, mp=mp, pp=pp, ep=ep,
                                         micro_batches=micro,
                                         zero_stage=zero,
                                         schedule=sched,
                                         bucket_size=bucket)
                            mem = self.cost_model.memory_per_device(
                                model, s)
                            if mem > self.cluster.hbm_bytes:
                                continue
                            t = self.cost_model.step_time(model, s)
                            # prefer simpler configs on near-ties (zero
                            # adds collectives; mp/pp/ep/zb add failure
                            # surface)
                            tie_break = (zero, mp, pp, ep,
                                         sched != "1f1b", bucket)
                            scored.append((t, tie_break, s, mem))
        if not scored:
            raise ValueError(
                "no feasible parallel strategy: model does not fit "
                f"{n} x {self.cluster.hbm_bytes / 1e9:.0f}GB devices")
        scored.sort(key=lambda r: (r[0], r[1]))
        if top_k == 1:
            return scored[0][2]
        return [r[2] for r in scored[:top_k]]


@dataclasses.dataclass
class TunedResult:
    """`tune()` output: the chosen strategy plus the prediction that a
    later measured run is checked against."""
    strategy: Strategy
    step_time: float
    predicted_mfu: float
    memory_bytes: float
    cluster: ClusterSpec
    calibrated: bool = False
    candidates: list = dataclasses.field(default_factory=list)


def tune(model: ModelSpec, cluster: Optional[ClusterSpec] = None,
         n_devices: Optional[int] = None, measurements: Optional[dict] = None,
         schedules=("1f1b", "zero_bubble"),
         bucket_sizes=(0, 1 << 24, 1 << 27), top_k=8,
         zero_stages=(0, 1, 2, 3)) -> TunedResult:
    """Measurement-driven placement search: optionally calibrate the
    cluster from a profiled run, then score every (dp, mp, pp, zero,
    micro, schedule, bucket_size) config and return the winner with its
    predicted MFU. Callers whose executor supports only a subset of
    ZeRO stages must pass that subset as `zero_stages` — clamping the
    WINNER after the search would execute a config the HBM-feasibility
    gate never admitted."""
    cluster = cluster or ClusterSpec()
    calibrated = False
    if measurements:
        cluster = CostModel(cluster).calibrate(model, measurements)
        calibrated = True
    tuner = StrategyTuner(cluster)
    ranked = tuner.search(model, n_devices, top_k=max(int(top_k), 2),
                          schedules=schedules, bucket_sizes=bucket_sizes,
                          zero_stages=zero_stages)
    best = ranked[0]
    cm = tuner.cost_model
    return TunedResult(
        strategy=best,
        step_time=cm.step_time(model, best),
        predicted_mfu=cm.predicted_mfu(model, best),
        memory_bytes=cm.memory_per_device(model, best),
        cluster=cluster,
        calibrated=calibrated,
        candidates=ranked)
