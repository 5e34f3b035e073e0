"""`paddle.distributed.fleet.utils` parity
(`python/paddle/distributed/fleet/utils/`): filesystem tools (fs.py
LocalFS/HDFSClient), log_util, and the hybrid-parallel gradient sync
helper (hybrid_parallel_util.py fused_allreduce_gradients)."""
from __future__ import annotations

import logging
import os
import shutil
import subprocess


# --------------------------------------------------------------- fs.py


class FSFileExistsError(Exception):
    pass


class FSFileNotExistsError(Exception):
    pass


class LocalFS:
    """`fs.py:120 LocalFS` — the full local toolset."""

    def ls_dir(self, fs_path):
        if not self.is_exist(fs_path):
            return [], []
        dirs, files = [], []
        for n in os.listdir(fs_path):
            (dirs if os.path.isdir(os.path.join(fs_path, n))
             else files).append(n)
        return dirs, files

    def mkdirs(self, fs_path):
        os.makedirs(fs_path, exist_ok=True)

    def is_file(self, fs_path):
        return os.path.isfile(fs_path)

    def is_dir(self, fs_path):
        return os.path.isdir(fs_path)

    def is_exist(self, fs_path):
        return os.path.exists(fs_path)

    def touch(self, fs_path, exist_ok=True):
        if self.is_exist(fs_path) and not exist_ok:
            raise FSFileExistsError(fs_path)
        open(fs_path, "a").close()

    def mv(self, src_path, dst_path, overwrite=False, test_exists=True):
        if test_exists and not self.is_exist(src_path):
            raise FSFileNotExistsError(src_path)
        if self.is_exist(dst_path) and not overwrite:
            raise FSFileExistsError(dst_path)
        shutil.move(src_path, dst_path)

    def upload(self, local_path, fs_path):
        shutil.copy(local_path, fs_path)

    def download(self, fs_path, local_path):
        shutil.copy(fs_path, local_path)

    def delete(self, fs_path):
        if self.is_dir(fs_path):
            shutil.rmtree(fs_path)
        elif self.is_file(fs_path):
            os.unlink(fs_path)

    def need_upload_download(self):
        return False

    def list_dirs(self, fs_path):
        return self.ls_dir(fs_path)[0]


class HDFSClient:
    """`fs.py HDFSClient` — shells out to the hadoop CLI exactly like
    the reference; raises up front if no hadoop binary is reachable."""

    def __init__(self, hadoop_home, configs=None, time_out=5 * 60 * 1000,
                 sleep_inter=1000):
        self._hadoop = os.path.join(hadoop_home, "bin", "hadoop")
        if not os.path.exists(self._hadoop):
            raise RuntimeError(f"hadoop binary not found: {self._hadoop}")
        self._timeout_s = time_out / 1000.0
        self._cfg = []
        for k, v in (configs or {}).items():
            self._cfg += ["-D", f"{k}={v}"]

    def _run(self, *args, check=False):
        out = subprocess.run([self._hadoop, "fs", *self._cfg, *args],
                             capture_output=True, text=True,
                             timeout=self._timeout_s)
        if check and out.returncode != 0:
            raise RuntimeError(
                f"hadoop fs {' '.join(args)} failed rc={out.returncode}: "
                f"{out.stderr.strip()[:500]}")
        return out.returncode, out.stdout

    def is_exist(self, fs_path):
        return self._run("-test", "-e", fs_path)[0] == 0

    def is_dir(self, fs_path):
        return self._run("-test", "-d", fs_path)[0] == 0

    def is_file(self, fs_path):
        return self.is_exist(fs_path) and not self.is_dir(fs_path)

    def ls_dir(self, fs_path):
        rc, out = self._run("-ls", fs_path)
        dirs, files = [], []
        for line in out.splitlines():
            parts = line.split()
            if len(parts) < 8:
                continue
            name = parts[-1].rsplit("/", 1)[-1]
            (dirs if parts[0].startswith("d") else files).append(name)
        return dirs, files

    def mkdirs(self, fs_path):
        self._run("-mkdir", "-p", fs_path, check=True)

    def delete(self, fs_path):
        self._run("-rm", "-r", fs_path, check=True)

    def upload(self, local_path, fs_path):
        self._run("-put", local_path, fs_path, check=True)

    def download(self, fs_path, local_path):
        self._run("-get", fs_path, local_path, check=True)

    def need_upload_download(self):
        return True


# ----------------------------------------------------------- log_util


logger = logging.getLogger("paddle_tpu.distributed.fleet")


def set_log_level(level):
    """Attach the stream handler lazily (libraries must not mutate
    global logging state at import; without basicConfig the root
    lastResort handler still prints warnings+)."""
    if not logger.handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)s %(message)s"))
        logger.addHandler(h)
        logger.propagate = False
    logger.setLevel(level)


# ------------------------------------------- hybrid_parallel_util.py


def build_grad_buckets(pairs, bucket_size):
    """Group (param, grad) pairs into per-dtype buckets of at most
    `bucket_size` payload bytes (a single grad larger than the bucket
    gets a bucket of its own). Order within a dtype is preserved —
    callers pass parameters in reverse-creation order so the first
    buckets hold the grads the backward pass finishes first."""
    by_dtype = {}
    for p, g in pairs:
        by_dtype.setdefault(str(g._data.dtype), []).append((p, g))
    buckets = []
    cap = max(int(bucket_size or 1), 1)
    for items in by_dtype.values():
        cur, cur_bytes = [], 0
        for p, g in items:
            nbytes = int(g._data.size) * g._data.dtype.itemsize
            if cur and cur_bytes + nbytes > cap:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append((p, g))
            cur_bytes += nbytes
        if cur:
            buckets.append(cur)
    return buckets


def fused_allreduce_gradients(parameter_list, hcg=None,
                              bucket_size=128 * 1024 * 1024,
                              scale=None):
    """`hybrid_parallel_util.py:191` parity: all-reduce every
    parameter's grad across the data-parallel world, FUSED into
    per-dtype flat buckets of at most `bucket_size` bytes — one
    collective per bucket instead of one per parameter (the
    EagerReducer bucketing the old implementation silently skipped).

    Under the single controller, grads on replicated params are already
    the GLOBAL sum (GSPMD inserts the psum inside the compiled step),
    so the device-world reduction is an identity — collective.
    all_reduce's per-rank-leading-axis heuristic must NOT run here (a
    grad whose dim0 happens to equal the device count would be summed
    away). Cross-PROCESS reduction (jax.distributed multi-host eager
    mode) still applies, and there `scale` defaults to the
    data-parallel world size: the reference's
    `_apply_collective_grads` divides the summed gradients by nranks
    (an unscaled sum would step with grads nranks(x) too large).

    The win on the eager multi-process path is the COLLECTIVE
    COUNT (n buckets instead of n params — each eager all_reduce is a
    synchronous host round-trip through jax.device_get, so fewer
    round-trips is the whole game; true wire/compute overlap is the
    compiled path's job, `hybrid_gpt grad_bucket_bytes`). Buckets are
    built in reverse-parameter order so the first one reduced is the
    first whose grads the backward finished."""
    import jax
    from ..core.tensor import Tensor
    from ..profiler import metrics as _metrics
    from . import collective as C
    multi_process = jax.process_count() > 1
    if scale is None and multi_process:
        if hcg is not None:
            scale = hcg.get_data_parallel_world_size()
        else:
            scale = jax.process_count()
        scale = float(scale) if scale and scale > 1 else None
    pairs = [(p, p.grad) for p in parameter_list
             if getattr(p, "grad", None) is not None]
    buckets = build_grad_buckets(list(reversed(pairs)), bucket_size)
    if _metrics._enabled:
        _metrics.GRAD_BUCKETS.labels("eager").set(len(buckets))
    for bucket in buckets:
        if multi_process:
            # ONE wire collective per bucket, reduced in place
            if len(bucket) == 1:
                C.all_reduce(bucket[0][1])
            else:
                C.all_reduce_coalesced([g for _, g in bucket])
        for p, g in bucket:
            if scale is not None:
                g = Tensor(g._data / scale)
            p.grad = g
