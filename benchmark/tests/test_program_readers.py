"""The readers of the program's own span tree (``request_phases``,
``program_events``, ``host_span_quantile``) on a hand-made registry whose
numbers are worked out here, and every cell's new metrics in a rehearsal."""

import json
import types

import numpy as np
import pytest

import run as bench_run
from conftest import bench

from thunder_tpu import observe

# the traced window on the registry's clock: [10 ms, 30 ms); the harness's
# clock reads 100 s where the registry's reads 0
W0_US, W1_US = 10_000.0, 30_000.0


@pytest.fixture
def ctx():
    observe.enable(clear=True)
    logged = []
    yield types.SimpleNamespace(
        clock_sync=(100.0, 0.0), t_trace_open=100.0 + W0_US / 1e6,
        t_trace_close=100.0 + W1_US / 1e6, log=logged.append, logged=logged,
        load=lambda kind, name: bench_run.load_module(kind, name))
    observe.disable()
    observe.reset()


def span(name, t0_us, t1_us, **args):
    observe.get_registry().spans.append(
        {"name": name, "cat": "serving:request", "ts_us": float(t0_us),
         "dur_us": float(t1_us - t0_us), "tid": 1, "id": None, "parent": None,
         "args": args})


def event(kind, ts_us, **fields):
    observe.get_registry().events.append(
        {"kind": kind, "ts_us": float(ts_us), **fields})


def first_token(request, ts_us, resident_us):
    event("serving_first_token", ts_us, request=request, ttft_ms=0.0,
          resident_us=float(resident_us))


def read(ctx, metric_or_reader, **args):
    return bench_run.load_module("readers", metric_or_reader).read(ctx, **args)


def test_request_phases_worked_by_hand(ctx):
    # request 1, the plain road (us): submitted 1000, admitted 3000, first
    # chunk dispatched 5000, a second 9000, resident 11000, token 14000
    #   queue 2.0  prefill_wait 2.0  prefill 6.0  first_decode 3.0   (ms)
    span("queued", 1000, 3000, request=1)
    span("prefill_chunk", 5000, 6500, request=1)
    span("prefill_chunk", 9000, 10500, request=1)
    first_token(1, 14000, 11000)
    # ... preempted while decoding, long after: not on the road to the token
    span("queued", 20000, 21000, request=1)
    # request 2, preempted in its first prefill: submitted 2000, admitted
    # 2500, a chunk at 3000, thrown out at 6000, admitted again 8000, chunk
    # 8500, resident 9500, token 12500. Everything before the LAST admission
    # is queue (6.0), the rest runs from it:
    #   queue 6.0  prefill_wait 0.5  prefill 1.0  first_decode 3.0
    span("queued", 2000, 2500, request=2)
    span("prefill_chunk", 3000, 4000, request=2)
    span("queued", 6000, 8000, request=2)
    span("prefill_chunk", 8500, 9300, request=2)
    first_token(2, 12500, 9500)
    # request 3 had its first token at 9000, before the window opened at
    # 10000: left out
    span("queued", 4000, 5000, request=3)
    span("prefill_chunk", 5500, 6000, request=3)
    first_token(3, 9000, 6500)
    # request 4 has no first token yet: left out
    span("queued", 12000, 15000, request=4)
    span("prefill_chunk", 15500, 16000, request=4)
    # request 5, a forked clone: pending 10000 -> 13000, resident as it
    # forks, no chunk of its own, token 16000
    #   queue 3.0  prefill_wait 0  prefill 0  first_decode 3.0
    span("queued", 10000, 13000, request=5)
    first_token(5, 16000, 13000)
    # means over requests 1, 2 and 5
    want = {"queue": (2.0 + 6.0 + 3.0) / 3, "prefill_wait": (2.0 + 0.5 + 0.0) / 3,
            "prefill": (6.0 + 1.0 + 0.0) / 3, "first_decode": 3.0}
    for phase, ms in want.items():
        assert read(ctx, "request_phases", phase=phase, stat="mean") == \
            pytest.approx(ms), phase
    assert read(ctx, "request_phases", phase="queue", stat="p50") == \
        pytest.approx(3.0)
    rp = bench_run.load_module("readers", "request_phases")
    reg = observe.get_registry()
    every = rp.phases(list(reg.spans), list(reg.events))
    assert sorted(every) == [1, 2, 3, 5]            # no window: 3 counts too
    assert sum(every[2].values()) == pytest.approx((12500 - 2000) / 1e3)
    with pytest.raises(ValueError):
        read(ctx, "request_phases", phase="decode")


def test_request_phases_read_nothing_from_a_program_without_them(ctx):
    """The parent program's event has no ``resident_us``: nothing to read,
    and the line leaves the metric out."""
    span("queued", 11000, 12000, request=1)
    event("serving_first_token", 15000, request=1, ttft_ms=4.0)
    assert read(ctx, "request_phases", phase="queue") is None
    ctx.clock_sync = None                           # an untraced run
    assert read(ctx, "request_phases", phase="queue") is None
    assert read(ctx, "program_events", kind="cache_miss") is None
    assert read(ctx, "host_span_quantile", names=["decode_wait"], q=95) is None


def test_host_span_quantile_is_nearest_rank_inside_the_window(ctx):
    # twenty waits of 1..20 ms inside the window; the 95th percentile by
    # nearest rank is sorted[int(20 * 0.95)] = sorted[19] = 20 ms, the 50th
    # sorted[10] = 11 ms. One of 99 ms before the window does not count.
    for i in range(20):
        t0 = W0_US + 1 + 900 * i
        span("decode_wait", t0, t0 + 1000.0 * (20 - i))
    span("decode_wait", 1000, 100_000)
    span("decode_build", W0_US + 5, W0_US + 50_005)
    assert read(ctx, "host_span_quantile", names=["decode_wait"], q=95) == \
        pytest.approx(20.0)
    assert read(ctx, "host_span_quantile", names=["decode_wait"], q=50) == \
        pytest.approx(11.0)
    assert read(ctx, "host_span_quantile", names=["decode_deliver"], q=95) is None


def test_program_events_counts_inside_the_window(ctx):
    event("cache_miss", 9_999, fn="step", reason="first")         # before
    event("cache_miss", 10_001, fn="step", reason="leaf 2 shape (4,) -> (8,)")
    event("serving_admitted", 11_000, request=1)                    # other kind
    event("cache_miss", 30_001, fn="step", reason="treedef")       # after
    assert read(ctx, "program_events", kind="cache_miss") == 1
    assert "leaf 2 shape" in str(ctx.logged)
    assert read(ctx, "program_events", kind="serving_preempt") == 0


def test_a_shape_changed_mid_window_is_one_miss_that_names_the_leaf(ctx):
    """``tt_cache_misses_in_window`` through its metric file, on the
    program's real events: a window in which one call comes with another
    shape reads 1, and the event says which leaf moved."""
    import thunder_tpu as tt
    from thunder_tpu import ops
    from thunder_tpu.observe.registry import _now_us

    how = bench_run.load_json("metrics", "tt_cache_misses_in_window.json")
    jf = tt.jit(lambda w, x: ops.mul(x, w).sum())
    w = np.float32(2.0)
    jf(w, np.ones((2, 8), np.float32))              # set-up: compiled
    ctx.clock_sync = (100.0, _now_us())             # the window opens here
    ctx.t_trace_open = 100.0
    jf(w, np.ones((2, 8), np.float32))              # hits
    jf(w, np.ones((2, 16), np.float32))             # the changed shape
    jf(w, np.ones((2, 16), np.float32))
    ctx.t_trace_close = 100.0 + (_now_us() - ctx.clock_sync[1]) / 1e6
    assert read(ctx, how["reader"], **how["args"]) == 1
    (miss,) = [e for e in observe.get_registry().events
               if e["kind"] == "cache_miss" and e["ts_us"] >= ctx.clock_sync[1]]
    assert miss["reason"] == "leaf 1 shape (2, 8) -> (2, 16)"


NEW = {
    "mistral7b_train": {"jit_call_host_ms_p50.train"},
    "mistral7b_serve_decode_sat": {
        "decode_host_ms_per_step.sat", "decode_enqueue_ms_p50.sat",
        "decode_wait_ms_p50.sat"},
    "mistral7b_serve_chat": {
        "decode_host_ms_per_step.chat", "decode_wait_ms_p95.chat",
        "ttft_queue_ms_mean.chat", "ttft_prefill_wait_ms_mean.chat",
        "ttft_prefill_ms_mean.chat", "ttft_first_decode_ms_mean.chat"},
}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_every_new_metric_is_printed_in_a_traced_rehearsal(manifest, tmp_path, cell):
    mine = {m["name"] for m in bench_run.cell_metrics(manifest, cell, "per_layer")}
    assert NEW[cell] | {"tt_cache_misses_in_window"} <= mine
    other = set().union(*(v for k, v in NEW.items() if k != cell))
    assert not other & mine
    r = bench("--workload", cell, "--seed", str(2**31 + 4242), "--seconds", "2",
              "--trace", "1", "--rehearse", "--out", str(tmp_path))
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    got = line["rehearsal_metrics"]
    assert line["correct"] is True and line["metrics"] == {}
    for name in NEW[cell]:
        assert got[name]["unit"] == "ms" and got[name]["value"] >= 0, name
    assert got["tt_cache_misses_in_window"] == {"value": 0, "unit": "count"}
    if cell == "mistral7b_serve_decode_sat":
        # the parts of a decode step add up to the step the harness timed
        step = got["decode_step_ms_p50.sat"]["value"]
        parts = got["decode_host_ms_per_step.sat"]["value"] \
            + got["decode_wait_ms_p50.sat"]["value"]
        assert parts == pytest.approx(step, rel=0.25)
