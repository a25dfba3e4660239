"""The program's spans in the benchmark: the readers of the span metrics,
and idle gaps named by the program span that covers them."""
import copy
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402
import devtrace  # noqa: E402
import program_trace  # noqa: E402

# one device plane; on the host, the benchmark's annotations and the
# program's spans: mergepipe.execute and mergepipe.exec.wait_input inside
# bench.run_all on the driving thread, mergepipe.bg.stage on a pool
# thread.  Times in ns, offsets in ps.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 15000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 25000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 19000000 }
%(driving)s
  }
  lines { id: 2 name: "mergepipe-read_0" timestamp_ns: 0
%(pool)s
  }
  event_metadata { key: 1 value { id: 1 name: "bench.submit" } }
  event_metadata { key: 2 value { id: 2 name: "bench.run_all" } }
  event_metadata { key: 3 value { id: 3 name: "mergepipe.execute" } }
  event_metadata { key: 4 value { id: 4 name: "mergepipe.exec.wait_input" } }
  event_metadata { key: 5 value { id: 5 name: "mergepipe.bg.stage" } }
}
"""
# execute 7-17 us, wait_input 10-13 us; bg.stage 11-12 us and 17-20 us
DRIVING = """
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 3000000 }
"""
POOL = """
    events { metadata_id: 5 offset_ps: 11000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 17000000 duration_ps: 3000000 }
"""


def _profile(driving=DRIVING, pool=POOL):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(
        TRACE % {"driving": driving, "pool": pool})


def test_gaps_take_the_innermost_program_span():
    got = program_trace.reduce(_profile())
    # gaps 8-15 us (middle 11.5: wait_input, inside execute; the shorter
    # bg.stage there is not the driving thread's), 16-21 us (middle 18.5:
    # only a bg.stage, so the benchmark's annotation), 1-3 us (middle 2:
    # no program span)
    assert got["idle_gaps"] == [
        ["mergepipe.exec.wait_input", pytest.approx(7e-6)],
        ["bench.run_all", pytest.approx(5e-6)],
        ["bench.run_all", pytest.approx(2e-6)]]


def test_a_gap_under_an_outer_span_takes_that_span():
    driving = DRIVING.replace("offset_ps: 10000000 duration_ps: 3000000",
                              "offset_ps: 7000000 duration_ps: 1000000")
    got = program_trace.reduce(_profile(driving=driving))
    assert got["idle_gaps"][0] == ["mergepipe.execute", pytest.approx(7e-6)]


def test_background_spans_never_label_a_gap():
    spans = program_trace.program_spans(_profile())
    assert sorted(n for n, _, _ in spans) == ["mergepipe.exec.wait_input",
                                              "mergepipe.execute"]
    assert program_trace.label([("bench.run_all", 0, 100)],
                               [("mergepipe.execute", 10, 20)], 50) == \
        "bench.run_all"


def test_program_spans_change_no_reading_of_the_trace():
    plain = devtrace.reduce(_profile(driving="", pool=""))
    for reduced in (devtrace.reduce(_profile()),
                    program_trace.reduce(_profile())):
        for key in ("busy_s", "window_s", "device_ops"):
            assert reduced[key] == plain[key], key
    assert [g[1] for g in program_trace.reduce(_profile())["idle_gaps"]] == \
        [g[1] for g in plain["idle_gaps"]]
    # without program spans the labels are the benchmark's, as before
    assert program_trace.reduce(_profile(driving="", pool="")) == plain


# ------------------------------------------------------------- readers
SPAN_METRICS = {
    "plan_s": "mergepipe.plan",
    "input_wait_s": "mergepipe.exec.wait_input",
    "host_prep_s": "mergepipe.exec.host_prep",
    "device_call_s": "mergepipe.exec.device",
    "write_wait_s": "mergepipe.exec.wait_write",
    "commit_s": "mergepipe.commit",
}
# the run of test_chipbench_manifest's reader test: no job carries a trace
NO_TRACE = {"setup_s": 1.0, "trace": None, "traced_merge_bytes": None,
            "peak": None, "batches": [{"wall_s": 2.0, "io_bytes": 10,
                                       "expert_bytes": 4,
                                       "jobs": [{"seconds": 1.5}]}]}


def _job_trace(seconds, h2d):
    return {"job": "j", "counters": {"h2d_bytes": h2d},
            "spans": {name: {"count": 1, "seconds": seconds,
                             "self_seconds": seconds}
                      for name in SPAN_METRICS.values()}}


def _run_with(traces):
    run = copy.deepcopy(NO_TRACE)
    run["batches"] = [{"wall_s": 4.0, "io_bytes": 10, "expert_bytes": 4,
                       "jobs": [dict(seconds=1.0, **t) for t in traces]}]
    return run


@pytest.mark.parametrize("name", sorted(SPAN_METRICS) + ["h2d_gb_per_merge"])
def test_span_readers(name):
    read = bench.reader(name)
    assert read(NO_TRACE) is None
    run = _run_with([{"trace": _job_trace(1.0, 2e9)},
                     {"trace": _job_trace(3.0, 4e9)}])
    assert read(run) == pytest.approx(3.0 if name == "h2d_gb_per_merge"
                                      else 2.0)
    # a window in which one job has no trace reads nothing
    run["batches"][0]["jobs"].append({"seconds": 1.0})
    assert read(run) is None


def test_readers_find_a_job_by_sid_among_the_programs_recent_jobs():
    from repro.core import trace

    rec = trace.Recorder("bench-spans-sid")
    with rec.span(trace.EXECUTE):
        with rec.span(trace.COMMIT):
            trace.count("h2d_bytes", 5 * 10 ** 8)
    rec.finish()
    run = _run_with([{"sid": "bench-spans-sid"}])
    commit = trace.recent("bench-spans-sid")["spans"][trace.COMMIT]["seconds"]
    assert bench.reader("commit_s")(run) == pytest.approx(commit)
    assert bench.reader("h2d_gb_per_merge")(run) == pytest.approx(0.5)
    # a span the job never entered reads 0
    assert bench.reader("device_call_s")(run) == 0.0
    assert bench.reader("commit_s")(_run_with([{"sid": "no-such-job"}])) \
        is None
