"""Measurement-driven Pallas kernel autotuner (ROADMAP item 2).

Three hand-tiled Pallas surfaces (flash, the paged-attention family,
the MoE grouped-expert matmul) carry grid/tile/pipeline numbers that
were picked once by hand — fastest on the author's box, frozen
thereafter. PAPERS.md's "Automatic Kernel Generation for Volta Tensor
Cores" and "CUDA-L2" both make the same observation: searched kernels
consistently beat hand-picked tiles, and the search is cheap compared
to the serving hours the winner runs for. This module makes the tile
numbers self-maintaining:

* **Search spaces** parameterize the tunable axes of each kernel
  entry — block/tile sizes (flash ``block_q``/``block_k``, splash's
  six block numbers, grouped-matmul ``block_c/f/d``), the KV blocks
  per compute step of the paged family (``kv_blocks``), and the
  engine-level KV ``block_size`` whose choice reshapes every paged
  tile.
* **Candidates are measured, not modeled**: `search()` times each
  admitted candidate with the PR 1 timer statistics (min over a
  window of repeats — the same `profiler.timer._Stat` the throughput
  benchmark uses) under a wall-clock budget.
* **Parity is the admission gate**: every candidate's output is
  checked against the caller's XLA oracle before it may be timed; a
  candidate that fails parity is rejected and counted
  (`paddle_tpu_kernel_autotune_candidates_rejected_parity_total`) —
  a fast wrong kernel must never win.
* **Winners are cached** per `(kernel, shape-bucket, dtype,
  backend/topology)` in a persistent on-disk JSON cache mirroring
  `parallel.auto_tuner`'s calibrated-placement discipline: measure
  once, replay forever. The repo ships a pre-seeded cache
  (`autotune_cache.json` next to this module) so the default CI path
  never tunes — a cache hit is ONE dict lookup (memoized in-process),
  zero search cost. Misses are recorded so
  `tools/kernel_coverage.py --tuner-audit` can flag shape-buckets
  that serve traffic without a tuned entry.

Env contract:

* ``PADDLE_TPU_KERNEL_AUTOTUNE=0`` — kill-switch: every consumer gets
  its hand-picked default, the cache is neither read nor written.
* ``PADDLE_TPU_KERNEL_AUTOTUNE=1`` (default) — cached winners apply;
  a miss falls back to the default (and is recorded for the audit).
* ``PADDLE_TPU_KERNEL_AUTOTUNE=tune`` — a miss additionally runs the
  registered search for that kernel (bounded by its time budget) and
  persists the winner: the re-tune-on-new-hardware path
  (docs/KERNELS.md).
* ``PADDLE_TPU_KERNEL_CACHE=<path>`` — a writable overlay over the
  seeded package cache, read and written only when the variable names
  it; unset, tuned winners live in-process and the committed seed file
  alone decides what a fresh process compiles.

Alignment single source of truth: `paged_alignment_ok` below is THE
definition of the paged kernels' shape constraints. The dispatch gate
(`paged_attention.paged_pallas_enabled`) and the tuner's candidate
filters both call it, so a tuned candidate can never be admitted that
the serve-time gate would refuse (ISSUE 11 satellite).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

# ---------------------------------------------------------------------
# alignment constraints — ONE source of truth for the dispatch gate
# (paged_attention.paged_pallas_enabled) AND every tuner candidate
# filter. In the [BS, H, Dh] KV tile head_dim rides the 128-wide lane
# axis and H the sublane axis; block_size is a leading axis. The
# run-major kernel (PR 26) folds a tile to [BS * H, Dh] rows and was
# compiled for a described v5e at H = 2, 8, 16, 32 (fp32 / bf16 pools)
# and, for int8 / fp8 pools, wherever BS * H fills whole lane tiles —
# the one head-count condition, which `paged_pallas_enabled` adds for
# quantized pools. The block_size multiple is kept as the tuner's
# candidate discipline, not because Mosaic needs it.
# ---------------------------------------------------------------------

LANE_ALIGN = 128
SUBLANE_ALIGN = 8


def paged_alignment_ok(head_dim, block_size) -> bool:
    """True when the paged Pallas kernels can tile this
    (head_dim, block_size) on real TPU hardware. The serve-time
    dispatch gate and the tuner's block-size candidate filter share
    this predicate by construction."""
    return int(head_dim) % LANE_ALIGN == 0 \
        and int(block_size) % SUBLANE_ALIGN == 0


# ---------------------------------------------------------------------
# mode / keys
# ---------------------------------------------------------------------

_ENV = "PADDLE_TPU_KERNEL_AUTOTUNE"


def mode() -> str:
    """"off" | "on" | "tune" from the env contract above."""
    v = os.environ.get(_ENV, "1").strip().lower()
    if v in ("0", "off", "false"):
        return "off"
    if v == "tune":
        return "tune"
    return "on"


def enabled() -> bool:
    return mode() != "off"


def backend_key() -> str:
    """Cache-key backend/topology component: platform + device kind +
    device count, so a cache tuned on one slice never silently applies
    to another (v5e-8 tiles are not v4-32 tiles — and neither are the
    CPU interpret-mode numbers the CI cache ships). The CPU backend
    drops the count: `--xla_force_host_platform_device_count` is a
    test-harness knob, not a topology."""
    import jax
    dev = jax.devices()[0]
    kind = "".join(c if c.isalnum() else "-" for c in dev.device_kind)
    if dev.platform == "cpu":
        return f"cpu-{kind}"
    return f"{dev.platform}-{kind}-d{jax.device_count()}"


def _pow2_bucket(n, lo=1):
    n = max(int(n), 1)
    p = int(lo)
    while p < n:
        p *= 2
    return p


def shape_bucket(*dims):
    """Bucket a shape tuple: every axis rounds up to a power of two,
    so nearby traffic shapes share one tuned entry (the engine's token
    budget and slot counts are already pow2-disciplined via
    `serving.batcher`, making the serving buckets exact)."""
    return tuple(_pow2_bucket(d) for d in dims)


def cache_key(kernel, bucket, dtype, backend=None) -> str:
    b = "x".join(str(int(d)) for d in bucket)
    return f"{kernel}|{b}|{np.dtype(dtype).name}|" \
           f"{backend or backend_key()}"


# ---------------------------------------------------------------------
# persistent cache: seeded package file + writable user overlay
# ---------------------------------------------------------------------

_SEED_CACHE_FILE = os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "autotune_cache.json")

_CACHE = None            # key -> {"config": {...}, ...}
_MEMO = {}               # key -> config (the one-dict-lookup hot path)
_REQUESTED = {}          # key -> bool hit (audit + stale detection)


def user_cache_path() -> str | None:
    """The writable overlay, if `PADDLE_TPU_KERNEL_CACHE` names one.
    There is no default location: without the variable, what gets
    compiled depends on the committed seed file alone — never on a
    file outside the tree that a fresh checkout would not have."""
    return os.environ.get("PADDLE_TPU_KERNEL_CACHE") or None


def _read_json(path):
    try:
        with open(path) as f:
            data = json.load(f)
        return dict(data.get("entries", {}))
    except (OSError, ValueError):
        return {}


def load_cache(refresh=False) -> dict:
    """The merged cache (seeded package entries under the overlay,
    when one is named). Loaded once per process; `refresh=True`
    re-reads disk."""
    global _CACHE
    if _CACHE is None or refresh:
        _CACHE = _read_json(_SEED_CACHE_FILE)
        overlay = user_cache_path()
        if overlay is not None:
            _CACHE.update(_read_json(overlay))
        _MEMO.clear()
    return _CACHE


def _persist(key, entry):
    path = user_cache_path()
    if path is None:
        return False        # no overlay named: the winner lives in-process
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        user = _read_json(path)
        user[key] = entry
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": 1, "entries": user}, f, indent=1,
                      sort_keys=True)
        os.replace(tmp, path)
        return True
    except OSError:
        return False


def record(kernel, bucket, dtype, config, meta=None, persist=True):
    """Cache a tuned winner (and persist it to the user cache file)."""
    key = cache_key(kernel, bucket, dtype)
    entry = {"config": dict(config)}
    if meta:
        entry["meta"] = dict(meta)
    load_cache()[key] = entry
    _MEMO[key] = dict(config)
    if persist:
        _persist(key, entry)
    return key


def _metrics():
    from ...profiler import metrics as pm
    return pm


def kernel_config(kernel, bucket, dtype, default=None):
    """The hot lookup every tuned kernel entry calls at TRACE time
    (inside the one compile — never per step): cached winner on hit,
    `default` on miss or with the kill-switch set. A hit is one dict
    probe; hits/misses are counted and every requested key is recorded
    for the stale-cache audit."""
    if not enabled():
        return default
    key = cache_key(kernel, bucket, dtype)
    cfg = _MEMO.get(key)
    if cfg is None:
        entry = load_cache().get(key)
        if entry is not None:
            cfg = _MEMO[key] = dict(entry["config"])
    hit = cfg is not None
    _REQUESTED[key] = hit or _REQUESTED.get(key, False)
    pm = _metrics()
    if pm._enabled:
        (pm.KERNEL_AUTOTUNE_CACHE_HITS if hit
         else pm.KERNEL_AUTOTUNE_CACHE_MISSES).labels(kernel).inc()
    return dict(cfg) if hit else default


def requested() -> dict:
    """Every cache key `kernel_config` was asked for this process,
    mapped to whether it ever hit — the audit's traffic record."""
    return dict(_REQUESTED)


def audit(requested_keys=None):
    """Stale-cache detection: cache keys traffic asked for that hold
    no tuned entry. Returns (missing_keys, hit_keys)."""
    req = requested() if requested_keys is None else {
        k: False for k in requested_keys}
    cache = load_cache()
    missing, hit = [], []
    for key in sorted(req):
        (hit if key in cache else missing).append(key)
    return missing, hit


def reset_for_tests():
    """Drop the in-process cache/memo/audit state (tests only)."""
    global _CACHE
    _CACHE = None
    _MEMO.clear()
    _REQUESTED.clear()


# ---------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------


class SearchResult:
    def __init__(self, config, seconds, tried, rejected, elapsed,
                 timings=None):
        self.config = config          # winning candidate (dict)
        self.seconds = seconds        # its measured time
        self.tried = tried            # candidates timed
        self.rejected = rejected      # candidates failing parity
        self.elapsed = elapsed        # wall seconds the search spent
        self.timings = timings or []  # [(config, seconds)] admitted

    def __repr__(self):
        return (f"SearchResult({self.config}, {self.seconds:.3e}s, "
                f"tried={self.tried}, rejected={self.rejected})")


def _default_timer(fn, args, repeats):
    """Min-of-window candidate pricing on the PR 1 timer statistics:
    one warmup call (compile), then `repeats` timed calls, min wins
    (host noise only ever inflates a sample)."""
    import jax
    from ...profiler.timer import _Stat

    def run():
        out = fn(*args)
        jax.tree_util.tree_map(
            lambda a: a.block_until_ready()
            if hasattr(a, "block_until_ready") else a, out)
        return out

    run()
    stat = _Stat()
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        run()
        stat.add(time.perf_counter() - t0)
    return min(stat.window)


def _parity_ok(out, ref, rtol, atol):
    import jax
    outs = jax.tree_util.tree_leaves(out)
    refs = jax.tree_util.tree_leaves(ref)
    if len(outs) != len(refs):
        return False
    for o, r in zip(outs, refs):
        o = np.asarray(o, np.float64)
        r = np.asarray(r, np.float64)
        if o.shape != r.shape or not np.allclose(o, r, rtol=rtol,
                                                 atol=atol):
            return False
    return True


def search(kernel, bucket, dtype, candidates, build, args, oracle,
           *, rtol=2e-2, atol=2e-2, budget_s=None, repeats=3,
           timer=None, persist=True, meta=None):
    """Measure candidates, gate each on oracle parity, cache the winner.

    candidates  ordered list of config dicts (deterministic: a fixed
                seed reproduces the same winner when the timer is
                deterministic — the replay property test injects one)
    build       config -> callable(*args), or -> (callable, args) when
                the candidate re-shapes its own inputs (the engine-
                level block-size axis); returning None SKIPS the
                candidate (the space's shape filter)
    oracle      callable(*args) -> the reference output the admission
                gate compares every candidate against (re-evaluated on
                a candidate's own args when build supplies them)
    budget_s    wall-clock budget; at least one admitted candidate is
                always evaluated, the rest are dropped once exceeded
    timer       (fn, args, repeats) -> seconds; injectable so tests
                (and the replay contract) can price deterministically

    Returns the `SearchResult`; the winner is recorded in the cache
    under `(kernel, bucket, dtype, backend)` unless `persist=False`
    wants a dry run (the result still carries it)."""
    timer = timer or _default_timer
    ref = oracle(*args) if args is not None else None
    t_start = time.perf_counter()
    best_cfg, best_t = None, float("inf")
    tried = rejected = 0
    timings = []
    pm = _metrics()
    for cfg in candidates:
        elapsed = time.perf_counter() - t_start
        if budget_s is not None and elapsed > budget_s and tried > 0:
            break
        built = build(dict(cfg))
        if built is None:
            continue
        if isinstance(built, tuple):
            fn, cand_args = built
        else:
            fn, cand_args = built, args
        cand_ref = ref if cand_args is args else oracle(*cand_args)
        # a candidate that fails to build or compile RAISES out of the
        # search: the spaces above only emit tiles that divide their
        # axes, so an exception here is a kernel or compiler fault, and
        # counting it as a parity rejection would hide it
        out = fn(*cand_args)
        if not _parity_ok(out, cand_ref, rtol, atol):
            rejected += 1
            if pm._enabled:
                pm.KERNEL_AUTOTUNE_REJECTED_PARITY.labels(kernel).inc()
            continue
        t = timer(fn, cand_args, repeats)
        tried += 1
        timings.append((dict(cfg), t))
        if t < best_t:
            best_cfg, best_t = dict(cfg), t
    elapsed = time.perf_counter() - t_start
    if pm._enabled:
        pm.KERNEL_AUTOTUNE_SEARCH_SECONDS.labels(kernel).inc(elapsed)
    if best_cfg is None:
        raise ValueError(
            f"kernel autotune: no candidate for '{kernel}' passed the "
            f"parity gate ({rejected} rejected)")
    info = {"seconds": best_t, "tried": tried, "rejected": rejected,
            "search_seconds": round(elapsed, 4)}
    if meta:
        info.update(meta)
    if persist:
        record(kernel, bucket, dtype, best_cfg, meta=info)
    return SearchResult(best_cfg, best_t, tried, rejected, elapsed,
                        timings)


#: kernel name -> searcher(bucket, dtype) -> SearchResult. Registered
#: lazily by `_default_searcher` so `ensure()` can run the matching
#: search on a miss under mode() == "tune" without the kernel modules
#: importing this one at definition time (they already do the reverse).
SEARCHERS = {}


def _default_searcher(kernel, bucket, dtype, budget_s):
    """The registered search for a kernel key, or None. These are the
    HOST-level entry points (engine build time, seed tool) — trace-time
    hooks stay cache-only so a jit trace never launches a search."""
    if not SEARCHERS:
        from . import flash_attention as _fa
        from . import grouped_matmul as _gmm
        from . import paged_attention as _pa
        SEARCHERS.update({
            "paged_ragged": lambda b, d, t: _pa.tune_paged_kernel(
                "paged_ragged", *b, dtype=d, budget_s=t),
            "paged_verify": lambda b, d, t: _pa.tune_paged_kernel(
                "paged_verify", *b, dtype=d, budget_s=t),
            "paged_decode": lambda b, d, t: _pa.tune_paged_kernel(
                "paged_decode", *b, dtype=d, budget_s=t),
            # block-sparse decode (ISSUE 15): 6-dim bucket — the last
            # axis is the shortened-table width (sparsity budget B)
            "paged_sparse": lambda b, d, t: _pa.tune_paged_sparse(
                *b, dtype=d, budget_s=t),
            "paged_block_size": lambda b, d, t: _pa.tune_block_size(
                *b, dtype=d, budget_s=t),
            "flash_fwd": lambda b, d, t: _fa.tune_flash(
                b[0], b[1], dtype=d, budget_s=t),
            "splash": lambda b, d, t: _fa.tune_splash(
                b[0], head_dim=(b[2] if len(b) > 2 else 128),
                dtype=d, budget_s=t),
            "grouped_matmul": lambda b, d, t: _gmm.tune_grouped_matmul(
                *b, dtype=d, budget_s=t),
        })
    fn = SEARCHERS.get(kernel)
    if fn is None:
        return None
    return lambda: fn(tuple(bucket), dtype, budget_s)


def ensure(kernel, bucket, dtype, default, searcher=None,
           budget_s=20.0):
    """Cache-or-default lookup with opt-in search-on-miss: a hit costs
    one dict probe (the zero-search-cost contract); a miss returns the
    default unless mode() == "tune", in which case the given `searcher`
    thunk — or the kernel's registered default search (`SEARCHERS`) —
    runs once under `budget_s` and its winner is cached. Callers on
    the serving path invoke this at BUILD time (before/outside the
    jitted step), so tuning never runs inside a trace."""
    cfg = kernel_config(kernel, bucket, dtype, default=None)
    if cfg is not None:
        return cfg
    if mode() == "tune":
        if searcher is None:
            searcher = _default_searcher(kernel, bucket, dtype,
                                         budget_s)
        if searcher is not None:
            # the operator asked for a search: one that cannot build,
            # compile or pass parity raises instead of quietly serving
            # the default
            return dict(searcher().config)
    return default


# ---------------------------------------------------------------------
# per-kernel search spaces (the tunable axes of each Pallas entry)
# ---------------------------------------------------------------------


def flash_candidates(seq_len, head_dim):
    """Hand flash-attention forward kernel: (block_q, block_k) tiles.
    Divisibility keeps the grid exact (the kernel refuses remainders);
    the default (256, 256) is always candidate 0 so an empty search
    can never lose it."""
    opts = [b for b in (128, 256, 512, 1024)
            if seq_len % b == 0 and b <= seq_len]
    if not opts:
        opts = [seq_len]
    cands = [{"block_q": 256, "block_k": 256}]
    for bq in opts:
        for bk in opts:
            c = {"block_q": bq, "block_k": bk}
            if c not in cands:
                cands.append(c)
    return cands


def splash_candidates(seq_len):
    """Splash attention: the six block sizes of `sk.BlockSizes`
    (fwd q/kv/kv_compute + fused-bwd dq/kv/kv_compute), the axes the
    r5 hand sweep walked one point of (`PADDLE_TPU_SPLASH_BLOCKS`)."""
    full = next((b for b in (1024, 512, 256, 128)
                 if seq_len % b == 0), seq_len)
    opts = sorted({min(b, full) for b in (128, 256, 512, full)})
    cands = []
    # current hand-picked default first (flash_attention._splash_kernel)
    bq0 = min(512, full)
    cands.append({"block_q": bq0, "block_kv": full,
                  "block_kv_compute": bq0, "block_q_dkv": bq0,
                  "block_kv_dkv": full, "block_kv_dkv_compute": full})
    for bq in opts:
        for bkvc in opts:
            c = {"block_q": bq, "block_kv": full,
                 "block_kv_compute": bkvc, "block_q_dkv": bq,
                 "block_kv_dkv": full, "block_kv_dkv_compute": bkvc}
            if c not in cands:
                cands.append(c)
    return cands


#: KV blocks per compute step of the run-major paged kernel: how many
#: pool blocks one double-buffered fetch brings and one MXU product a
#: plane of heads attends. Absent a cached winner the kernel picks from
#: its shapes (`paged_attention.run_tiles`: 16 at BS = 16 — 256 keys —
#: for planes of one or two heads, whatever H).
PAGED_KV_BLOCKS = (4, 8, 16)


def paged_candidates():
    return [{"kv_blocks": g} for g in PAGED_KV_BLOCKS]


def paged_block_size_candidates(head_dim, max_seq_len=None):
    """Engine-level KV block-size axis (`ServingEngine(block_size=
    "auto")`): every candidate must satisfy the SAME alignment
    predicate the serve-time dispatch gate enforces — a tuned block
    size the gate would refuse can never be admitted, by construction
    (they share `paged_alignment_ok`). Sublane alignment is enforced
    even when tuning on a backend whose XLA path would accept any
    size: a CPU-tuned cache must stay admissible on the TPU gate.
    (`head_dim` is part of the bucket identity but does not constrain
    the block-size axis — the predicate factors per axis.)"""
    del head_dim
    cands = []
    for bs in (8, 16, 32, 64):
        if max_seq_len is not None and bs > max_seq_len:
            continue
        if not paged_alignment_ok(LANE_ALIGN, bs):
            continue
        cands.append({"block_size": bs})
    return cands or [{"block_size": 16}]


def grouped_matmul_candidates(E, C, D, F):
    """Grouped-expert matmul: (block_c, block_f, block_d) tiles over
    the (expert, capacity, out-features) grid with a sequential
    D-reduction axis. Targets clamp to the largest divisor of the
    axis, so every candidate tiles exactly."""
    def divisors(n, targets):
        out = []
        for t in targets:
            d = min(t, n)
            while n % d:
                d -= 1
            if d >= 1 and d not in out:
                out.append(d)
        return out

    cands = []
    for bc in divisors(C, (128, 256, 512, C)):
        for bf in divisors(F, (128, 256, 512, F)):
            for bd in divisors(D, (256, 512, D)):
                c = {"block_c": bc, "block_f": bf, "block_d": bd}
                if c not in cands:
                    cands.append(c)
    return cands


SEARCH_SPACES = {
    "flash_fwd": flash_candidates,
    "splash": splash_candidates,
    "paged_ragged": paged_candidates,
    "paged_verify": paged_candidates,
    "paged_decode": paged_candidates,
    "paged_sparse": paged_candidates,
    "paged_block_size": paged_block_size_candidates,
    "grouped_matmul": grouped_matmul_candidates,
}
