"""Both cells end to end with `--rehearse` (CPU, tiny sizes, kernels
interpreted), traced and untraced, and the traffic generators. A
rehearsal debugs the harness; it measures nothing."""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import traffic  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def names(group, cell):
    return {m["name"] for m in MANIFEST[group]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace):
    env = dict(os.environ, BENCH_RUN="ignored")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell,
         "--seed", str(2 ** 31 + 11), "--seconds", "3", "--trace",
         str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert "CPU REHEARSAL" in lines[0] and "no measurement" in lines[-2]
    res = json.loads(lines[-1])
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(res) == (want | {"breakdown"} if trace else want)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    dev = {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        dev |= {"busy_s", "window_s"}
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        # a reader that finds nothing returns nothing (no Mosaic kernel
        # runs on the CPU); what is reported is of this cell
        assert res["metrics"]
        assert set(res["metrics"]) <= names("per_layer", cell)
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        for rows in res["breakdown"].values():
            assert len(rows) <= 10
            assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    else:
        assert set(res["metrics"]) == names("end_to_end", cell)
    assert set(res["device"]) == dev
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_poisson_mix_rehearses_and_reports_lateness():
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "serve_gpt3_1p3b_closed", "--traffic", "chat_poisson_rehearsal",
         "--seed", "3", "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "generator lateness ms p50" in out.stdout
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["attempted"] >= 5
    assert "kv pool:" in out.stdout and "preemptions" in out.stdout
    # the override is for rehearsals alone
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "serve_gpt3_1p3b_closed", "--traffic", "chat_poisson_rehearsal",
         "--seed", "3", "--seconds", "3", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2 and "{" not in out.stdout


def test_no_accelerator_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert not out.stdout.strip().splitlines()[-1].startswith("{")


def mix(generator, **more):
    base = {"generator": generator, "pool": 16, "pool_seed": 7,
            "prompt_len": {"dist": "lognormal", "median": 256,
                           "sigma": 0.8, "min": 32, "max": 1024},
            "output_len": {"dist": "lognormal", "median": 64,
                           "sigma": 0.6, "min": 16, "max": 256},
            "sentinel": {"prompt_len": 64, "output_len": 32}}
    return dict(base, **more)


@pytest.mark.parametrize("generator", ("closed_loop", "poisson"))
def test_same_seed_same_requests(generator):
    tr = mix(generator, rate_per_s=5.0)
    a = traffic.RequestSource(tr, 50304, 2048, 2 ** 31 + 5)
    b = traffic.RequestSource(tr, 50304, 2048, 2 ** 31 + 5)
    c = traffic.RequestSource(tr, 50304, 2048, 9)
    ra, rb, rc = ([s.request(i) for i in range(40)] for s in (a, b, c))
    assert [(r.prompt, r.max_new_tokens, r.due) for r in ra] == \
        [(r.prompt, r.max_new_tokens, r.due) for r in rb]
    assert [r.prompt for r in ra] != [r.prompt for r in rc]
    # every seed gets the same sizes in the same order: one cycle of
    # the pool is the pool
    sizes = lambda rs: [(len(r.prompt), r.max_new_tokens)  # noqa
                        for r in rs]
    assert sizes(ra) == sizes(rc)
    assert sorted(sizes(ra[:16])) == sorted(a.sizes)
    for r in ra:
        assert 32 <= len(r.prompt) <= 1024
        assert 16 <= r.max_new_tokens <= 256
        assert all(0 <= t < 50304 for t in r.prompt)
    assert a.sentinel().prompt == b.sentinel().prompt
    assert len(a.sentinel().prompt) == 64
    if generator == "poisson":
        due = [r.due for r in ra]
        assert all(x < y for x, y in zip(due, due[1:]))
        # one cycle of the gap pool sums the same for every seed, and
        # its mean gap is close to 1 / rate
        assert due == [r.due for r in rc]
        assert ra[15].due / 16 == pytest.approx(0.2, rel=0.15)
    else:
        assert all(r.due is None for r in ra)


def test_the_order_is_the_mix_s_not_the_seed_s():
    tr = mix("closed_loop")
    a = traffic.RequestSource(tr, 50304, 2048, 1)
    b = traffic.RequestSource(tr, 50304, 2048, 2 ** 31 + 5)
    ra, rb = ([s.request(i) for i in range(40)] for s in (a, b))
    assert [(len(r.prompt), r.max_new_tokens) for r in ra] == \
        [(len(r.prompt), r.max_new_tokens) for r in rb]
    assert [r.prompt for r in ra] != [r.prompt for r in rb]
    # each cycle of the pool is dealt in an order of its own
    assert [len(r.prompt) for r in ra[:16]] != \
        [len(r.prompt) for r in ra[16:32]]
    other = traffic.RequestSource(dict(tr, pool_seed=8), 50304, 2048, 1)
    assert [len(other.request(i).prompt) for i in range(16)] != \
        [len(r.prompt) for r in ra[:16]]


def test_quantiles_and_batches():
    d = {"dist": "lognormal", "median": 64, "sigma": 0.6, "min": 16,
         "max": 256}
    pool = traffic.size_pool(d, 64)
    assert pool == sorted(pool) and pool[0] >= 16 and pool[-1] <= 256
    assert 60 <= pool[32] <= 68
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "zipf"}, 0.3)
    specs = traffic.fixed_batches(
        {"generator": "fixed_batches", "batch": 32, "seq_len": 1024,
         "distinct_batches": 8}, 2 ** 31 + 5)
    assert len(specs) == 8
    assert len({s["key_seed"] for s in specs}) == 8
    assert all(0 <= s["key_seed"] < 2 ** 31 for s in specs)
    over = traffic.with_rehearsal(
        {"a": 1, "b": {"x": 1, "y": 2}, "rehearse": {"b": {"x": 9}}},
        True)
    assert over == {"a": 1, "b": {"x": 9, "y": 2}}
    assert traffic.with_rehearsal({"a": 1, "rehearse": {"a": 2}},
                                  False) == {"a": 1}
