"""``lib/xplane.py`` on a small trace recorded on the chip in PR 23
(``data/trace_logbert_saturate.json``: three scoring calls of
``logbert-256x4`` at full width, trimmed) and on hand-made intervals."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import os

import pytest

from bench_helpers import REPO, read_json
from benchmark.lib import layers, xplane

FIXTURE = os.path.join(REPO, "tests", "benchmark_tests", "data",
                       "trace_logbert_saturate.json")


def test_union_and_gaps_by_hand():
    intervals = [(0, 10), (5, 12), (20, 30), (22, 25)]
    assert xplane.union_ns(intervals) == 22
    assert xplane.gaps_ns(intervals, -3, 40) == [3, 8, 10]
    assert xplane.union_ns([]) == 0.0
    assert xplane.gaps_ns([], 0, 7) == [7]


def test_nested_ops_are_not_counted_twice():
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["%while", 0.0, 1e9],
                                       ["%fusion.1", 1e8, 2e8],
                                       ["%fusion.2", 1.5e9, 5e8]]},
        {"name": "XLA Modules", "events": [["jit_f(1)", 0.0, 2e9]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [["start_trace", -3e9, 1e9]]}]}]}
    out = xplane.reduce(trace)
    assert out["busy_s"] == pytest.approx(1.5)
    assert out["window_s"] == pytest.approx(2.0)
    assert out["idle_gaps"][0] == ["unattributed", pytest.approx(0.5)]
    assert out["modules"]["jit_f(1)"] == {
        "count": 1, "total_s": 2.0, "median_s": 2.0, "whole_count": 1,
        "whole_total_s": 2.0}


def _one_device(ops: list, modules: list, host: list = ()) -> dict:
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": list(host)}]}]}


def test_control_flow_wrappers_leave_the_list_of_operations():
    """A ``while`` or a ``conditional`` holds its body's operations, which
    have events of their own: listed, the wrapper would stand above every
    kernel and count their time a second time. Busy time and scopes' self
    time are what they were."""
    ops = [
        ["%while.3 = (s32[], f32[8]) while((s32[], f32[8]) %tuple.1), "
         "condition=%cond, body=%body", 0.0, 6e6, "m/moe", "while"],
        ["%ragged-dot.1 = f32[8] custom-call(f32[8] %x)", 1e6, 4e6,
         "m/moe", "custom-call"],
        ["%conditional.2 = f32[8] conditional(pred[] %p, f32[8] %a, "
         "f32[8] %b), true_computation=%t, false_computation=%f", 6e6, 3e6,
         "m/moe", "conditional"],
        ["%fusion.9 = f32[8] fusion(f32[8] %a)", 7e6, 1e6, "m/moe",
         "loop fusion"],
        # told by its text where the events carry no category
        ["%while.4 = (s32[]) while((s32[]) %t), condition=%c, body=%b",
         9e6, 1e6],
    ]
    reduced = xplane.reduce(_one_device(ops, [["jit_f(1)", 0.0, 10e6]]))
    names = [name for name, _ in reduced["device_ops"]]
    assert [name.split(" = ")[0] for name in names] == [
        "m/moe: %ragged-dot.1", "m/moe: %fusion.9"]
    assert reduced["busy_s"] == pytest.approx(10e-3)
    assert reduced["scopes"]["m/moe"]["self_s"] == pytest.approx(9e-3)
    assert xplane.wraps_others(["%fusion.1 = f32[8] fusion(f32[8] %while.3)",
                                0.0, 1.0]) is False


def test_gaps_under_a_millisecond_leave_the_list_of_idle_gaps():
    """What lies between two operations of one call is no gap anyone waits
    out; a gap that stays is still named by the annotation that covers at
    least half of it, and by none where none does."""
    ops = [["%a = f32[8] fusion(f32[8] %p)", 0.0, 1e6],
           ["%b = f32[8] fusion(f32[8] %p)", 1.0e6 + 9e3, 1e6],    # 9 us on
           ["%c = f32[8] fusion(f32[8] %p)", 4.009e6, 1e6],        # 2 ms on
           ["%d = f32[8] fusion(f32[8] %p)", 5.009e6 + 0.99e6, 1e6],
           ["%e = f32[8] fusion(f32[8] %p)", 10.999e6, 1e6]]       # 4 ms on
    host = [["dm.featurize#batch=3,rows=256#", 2.1e6, 0.75e6],
            ["dm.recv_wait", 7.0e6, 3.9e6]]
    reduced = xplane.reduce(_one_device(ops, [["jit_f(1)", 0.0, 12e6]],
                                        host))
    assert reduced["idle_gaps"] == [["dm.recv_wait", pytest.approx(4e-3)],
                                    ["unattributed", pytest.approx(2e-3)]]
    # an annotation's name ends where its arguments begin; one that covers
    # under half of a gap does not name it
    assert reduced["idle_gap_cover"][1] == {
        "dm.featurize": pytest.approx(0.375)}
    assert all(seconds >= xplane.MIN_GAP_S
               for _, seconds in reduced["idle_gaps"])
    # the idle share counts every gap, listed or not
    assert reduced["window_s"] - reduced["busy_s"] == pytest.approx(
        6.999e-3)


def test_a_trace_without_a_device_plane_reads_nothing():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["f", 0.0, 1e6]]}]}]}
    out = xplane.reduce(trace)
    assert out["devices"] == 0 and out["busy_s"] == 0.0
    assert layers.evaluate({"kind": "trace", "reducer": "device_idle_share"},
                           {"trace": out}) is None
    assert layers.evaluate({"kind": "trace", "reducer": "step_roofline_share"},
                           {"trace": out}) is None


class TestRecordedTrace:
    @pytest.fixture(scope="class")
    def reduced(self):
        return xplane.reduce(read_json(FIXTURE))

    def test_inventory(self, reduced):
        assert reduced["devices"] == 1
        assert ["/device:TPU:0", "XLA Modules", 3] in reduced["inventory"]

    def test_three_scoring_calls_of_about_314_ms(self, reduced):
        (name,) = reduced["modules"]
        assert name.startswith("jit__score_impl(")
        calls = reduced["modules"][name]
        # the capture's edge cut the first call short: two whole calls
        assert calls["count"] == 3 and calls["whole_count"] == 2
        assert calls["median_s"] == pytest.approx(0.313824693, rel=1e-9)

    def test_busy_time_is_the_modules_time(self, reduced):
        # back-to-back ops inside each call: the union of the op intervals
        # is the calls' own time to a few microseconds
        (calls,) = reduced["modules"].values()
        assert reduced["busy_s"] == pytest.approx(calls["total_s"], abs=1e-4)
        # the host events 0.1 s before and after are outside the window
        assert reduced["window_s"] == pytest.approx(calls["total_s"],
                                                    abs=1e-4)

    def test_back_to_back_calls_leave_only_microsecond_gaps(self, reduced):
        # … which are under ``MIN_GAP_S`` and so not listed; the idle share
        # still counts them (busy time against the window)
        assert reduced["idle_gaps"] == [] == reduced["idle_gap_cover"]
        assert 0 < reduced["window_s"] - reduced["busy_s"] < 1e-4

    def test_the_head_dominates(self, reduced):
        top = [name for name, _ in reduced["device_ops"][:3]]
        assert top[0].startswith("%while")
        assert any("f32[16384,32768]" in name for name in top)
        assert all(len(name) <= xplane.NAME_CHARS for name in top)

    def test_roofline_share_of_the_recorded_calls(self, reduced):
        ctx = {"trace": reduced, "capture_buckets": [16384],
               "scorer": {"model": "logbert", "vocab_size": 32768,
                          "dim": 256, "depth": 4, "heads": 4, "seq_len": 32},
               "peak": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}
        share = layers.evaluate({"kind": "trace",
                                 "reducer": "step_roofline_share"}, ctx)
        # 16384 rows * 742,391,808 ops / 197e12 = 61.74 ms of 313.8 ms
        assert share == pytest.approx(19.67, abs=0.05)
        assert share < 100


def test_the_recorded_trace_reduces_as_it_did():
    """``busy_s``, ``window_s`` and ``modules`` of the trace recorded in PR 23,
    to the digit what the reduction gave before it knew scopes (PR 26)."""
    reduced = xplane.reduce(read_json(FIXTURE))
    assert reduced["busy_s"] == 0.662275351
    assert reduced["window_s"] == 0.662292696
    assert reduced["modules"] == {
        "jit__score_impl(3228638487417728264)": {
            "count": 3, "total_s": 0.6622768689999999,
            "median_s": 0.313824693, "whole_count": 2,
            "whole_total_s": 0.6276838659999999}}
    # events without metadata: no scope is known, and nothing is made up
    assert "scopes" not in reduced and "module_scopes" not in reduced
    # the einsum head of PR 23 ran no kernel of the program's; XLA's own
    # two-nanosecond custom calls are counted under their name
    assert set(reduced["kernels"]) == {"custom-call"}


# -- an XSpace built here: scopes, a kernel, an annotated and a bare gap ----

MS = 1_000_000_000      # picoseconds in a millisecond
OPS = [
    # metadata id, offset in a call (ms), duration (ms)
    (1, 0.0, 0.4),      # under Model/blocks_0/layer0/attn
    (2, 0.4, 0.5),      # the custom call, under head/nll/lse_pallas
    (3, 0.9, 0.1),      # a copy without a name stack
]
CALL_STARTS_MS = [0.0, 2.0, 3.8]


def _event(metadata_id: int, start_ms: float, ms: float) -> str:
    return (f"events {{ metadata_id: {metadata_id} offset_ps: "
            f"{int(start_ms * MS)} duration_ps: {int(ms * MS)} }}\n")


def _xspace_text() -> str:
    ops = "".join(_event(mid, call + off, ms) for call in CALL_STARTS_MS
                  for mid, off, ms in OPS)
    calls = "".join(_event(9, call, 1.0) for call in CALL_STARTS_MS)
    host = (_event(1, 1.05, 0.9)        # dm.recv_wait over 90% of the 1st gap
            + _event(2, 3.0, 0.3)       # dm.featurize over 37.5% of the 2nd
            + _event(3, 0.0, 5.0))      # not one of the program's
    return f"""
planes {{
  name: "/device:TPU:0"
  lines {{ name: "XLA Modules" {calls} }}
  lines {{ name: "XLA Ops" {ops} }}
  event_metadata {{ key: 1 value {{ id: 1
    name: "%fusion.1 = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kOutput"
    stats {{ metadata_id: 1
      str_value: "jit(_score_impl)/Model/blocks_0/layer0/attn/dot_general:" }}
    stats {{ metadata_id: 2 str_value: "convolution fusion" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2
    name: "%lse_pallas.1 = f32[1,8]{{1,0}} custom-call(bf16[8,4]{{1,0}} %h)"
    stats {{ metadata_id: 1
      str_value: "jit(_score_impl)/head/nll/lse_pallas/pallas_call:" }}
    stats {{ metadata_id: 2 str_value: "custom-call" }} }} }}
  event_metadata {{ key: 3 value {{ id: 3
    name: "%copy.3 = f32[8]{{0}} copy(f32[8]{{0}} %x)"
    stats {{ metadata_id: 2 str_value: "data formatting" }} }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "jit__score_impl(1)" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "hlo_category" }} }}
}}
planes {{
  name: "/host:CPU"
  lines {{ name: "python" {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "dm.recv_wait" }} }}
  event_metadata {{ key: 2 value {{ id: 2
    name: "dm.featurize#batch=3,rows=256#" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "PjitFunction(f)" }} }}
}}
"""


@pytest.fixture()
def capture_dir(tmp_path):
    import jax

    data = jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        _xspace_text())
    folder = tmp_path / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(data)
    return str(tmp_path)


class TestBuiltXSpace:
    @pytest.fixture()
    def reduced(self, capture_dir):
        if xplane.xplane_pb2() is None:
            pytest.skip("no xplane_pb2 can be imported here")
        return xplane.reduce(xplane.load(capture_dir))

    def test_what_was_read_before_reads_the_same(self, reduced):
        assert reduced["devices"] == 1
        assert reduced["busy_s"] == pytest.approx(3.0e-3)
        assert reduced["window_s"] == pytest.approx(4.8e-3)
        assert reduced["modules"]["jit__score_impl(1)"]["count"] == 3

    def test_device_self_time_by_scope(self, reduced):
        assert set(reduced["scopes"]) == {
            "Model/blocks_0/layer0/attn", "head/nll/lse_pallas", "no scope"}
        attn = reduced["scopes"]["Model/blocks_0/layer0/attn"]
        assert attn == {"self_s": pytest.approx(1.2e-3), "events": 3}
        assert reduced["scopes"]["no scope"]["self_s"] == pytest.approx(
            0.3e-3)
        per_call = reduced["module_scopes"]["jit__score_impl(1)"]
        assert per_call["head/nll/lse_pallas"] == pytest.approx(1.5e-3)
        assert sum(per_call.values()) == pytest.approx(reduced["busy_s"])

    def test_every_custom_call_by_kernel_name(self, reduced):
        assert reduced["kernels"] == {"lse_pallas": {"jit__score_impl(1)": {
            "seconds": pytest.approx(1.5e-3), "count": 3}}}

    def test_operations_carry_their_scope(self, reduced):
        name, seconds = reduced["device_ops"][0]
        assert name.startswith("head/nll/lse_pallas: %lse_pallas.1 = ")
        assert seconds == pytest.approx(1.5e-3)
        assert reduced["device_ops"][2][0].startswith("no scope: %copy.3")
        assert all(len(name) <= xplane.NAME_CHARS
                   for name, _ in reduced["device_ops"])

    def test_idle_gaps_are_named_by_the_annotation_that_covers_them(
            self, reduced):
        # the second gap, 0.8 ms, is under ``MIN_GAP_S`` and not listed
        assert reduced["idle_gaps"] == [
            ["dm.recv_wait", pytest.approx(1.0e-3)]]
        (first,) = reduced["idle_gap_cover"]
        assert first == {"dm.recv_wait": pytest.approx(0.9)}

    def test_the_trace_metrics_read_it(self, reduced):
        ctx = {"trace": reduced}
        assert layers.evaluate(
            {"kind": "trace", "reducer": "scope_share",
             "scopes": ["layer*/attn"]}, ctx) == pytest.approx(40.0)
        assert layers.evaluate(
            {"kind": "trace", "reducer": "scope_share",
             "scopes": ["head/nll*"]}, ctx) == pytest.approx(50.0)


def test_without_xplane_pb2_the_new_readings_are_left_out(capture_dir,
                                                           monkeypatch):
    """Where nothing installed carries the XSpace protobuf, every reading of
    before stands and scopes are absent: a scope metric reports nothing."""
    monkeypatch.setattr(xplane, "xplane_pb2", lambda: None)
    trace = xplane.load(capture_dir)
    assert all(len(event) == 3 for plane in trace["planes"]
               for line in plane["lines"] for event in line["events"])
    reduced = xplane.reduce(trace)
    assert reduced["busy_s"] == pytest.approx(3.0e-3)
    assert reduced["window_s"] == pytest.approx(4.8e-3)
    assert reduced["modules"]["jit__score_impl(1)"]["whole_count"] == 3
    assert "scopes" not in reduced and "module_scopes" not in reduced
    assert reduced["device_ops"][0][0].startswith("%lse_pallas.1 = ")
    # the host's annotations and a custom call's name need no metadata
    assert reduced["idle_gaps"][0][0] == "dm.recv_wait"
    assert reduced["kernels"]["lse_pallas"]["jit__score_impl(1)"][
        "count"] == 3
    assert layers.evaluate({"kind": "trace", "reducer": "scope_share",
                            "scopes": ["layer*/attn"]},
                           {"trace": reduced}) is None
    assert layers.evaluate({"kind": "trace", "reducer": "device_idle_share"},
                           {"trace": reduced}) == pytest.approx(37.5)


def test_an_unimportable_protobuf_module_is_no_error(monkeypatch):
    monkeypatch.setattr(xplane, "XPLANE_PB2", ("no_such_package.xplane_pb2",))
    assert xplane.xplane_pb2() is None


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(_score_impl)/LogBERT.hidden/blocks_0/layer0/attn/qkv/dot_general:",
     "LogBERT.hidden/blocks_0/layer0/attn/qkv"),
    ("jit(_score_impl)/head/nll/lse_pallas/pallas_call:",
     "head/nll/lse_pallas"),
    ("jit(f)/jit(g)/embed/tok_embed/jit(_take)/gather:",
     "embed/tok_embed/jit(_take)"),
    ("jit(f)/head/nll/reshape;jit(f)/head/nll/lse_pallas/squeeze:",
     "head/nll"),
    ("jit(f)/mul:", "no scope"),
])
def test_scope_of_a_name_stack(tf_op, scope):
    assert xplane.scope_of(tf_op) == scope


def test_self_time_leaves_out_nested_operations():
    events = [["%while", 0.0, 100.0], ["%body.1", 10.0, 30.0],
              ["%inner", 15.0, 5.0], ["%body.2", 50.0, 50.0],
              ["%after", 100.0, 7.0]]
    assert xplane.self_ns(events) == [20.0, 25.0, 5.0, 50.0, 7.0]
    assert sum(xplane.self_ns(events)) == xplane.union_ns(
        [(start, start + length) for _, start, length in events])


@pytest.mark.parametrize("event, kernel", [
    (["%lse_pallas.1 = f32[1,8]{1,0} custom-call(bf16[8,4] %h), custom_call"
      "_target=\"tpu_custom_call\"", 0.0, 1.0], "lse_pallas"),
    (["%flash_fwd = f32[8] custom-call(f32[8] %q)", 0.0, 1.0, "a/b",
      "custom-call"], "flash_fwd"),
    (["%fusion.7 = f32[8] fusion(f32[8] %custom-call.2)", 0.0, 1.0], None),
    (["%fusion.7 = f32[8] custom-call(f32[8] %x)", 0.0, 1.0, "a",
      "loop fusion"], None),
])
def test_kernel_of_an_event(event, kernel):
    assert xplane.kernel_of(event) == kernel


def test_cover_of_a_gap_by_hand():
    by_name = {"dm.recv_wait": [(0.0, 40.0), (30.0, 60.0), (90.0, 200.0)],
               "dm.send": [(500.0, 600.0)]}
    assert xplane.cover((20.0, 120.0), by_name) == {"dm.recv_wait": 0.7}
    assert xplane.gap_spans_ns([(0, 10), (20, 30)], -3, 40) == [
        (-3, 0), (10, 20), (30, 40)]


def test_a_call_the_capture_cut_is_left_out_of_scopes_and_kernels():
    """Three calls of two operations each, under scopes a then b; the
    capture ends inside the third, after a. The cut call is 60% of a whole
    one: it stays out of ``module_scopes`` and ``kernels`` (its a without
    its b would tilt every share), while ``modules`` counts it by its older
    rule and ``scopes`` holds every operation."""
    def call(at, cut=False):
        ops = [["%fusion.1 = f32[8] fusion(f32[8] %p)", at, 60.0, "m/a",
                "loop fusion"]]
        if not cut:
            ops.append(["%k.1 = f32[8] custom-call(f32[8] %x)", at + 60.0,
                        40.0, "m/b", "custom-call"])
        return ops, ["jit__score_impl(1)", at, 60.0 if cut else 100.0]

    calls = [call(0.0), call(200.0), call(400.0, cut=True)]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [op for ops, _ in calls for op in ops]},
        {"name": "XLA Modules", "events": [module for _, module in calls]}]}]}
    reduced = xplane.reduce(trace)
    assert reduced["modules"]["jit__score_impl(1)"]["whole_count"] == 3
    assert reduced["scopes"]["m/a"] == {"self_s": pytest.approx(180e-9),
                                        "events": 3}
    assert reduced["module_scopes"] == {"jit__score_impl(1)": {
        "m/a": pytest.approx(120e-9), "m/b": pytest.approx(80e-9)}}
    assert reduced["kernels"] == {"k": {"jit__score_impl(1)": {
        "seconds": pytest.approx(80e-9), "count": 2}}}


# -- a failed capture prints no result (run.py) -------------------------------

def test_a_capture_that_ended_in_error_ends_the_run_with_its_error():
    from benchmark import run
    from benchmark.lib.stages import HarnessFailure

    run.refuse_failed_capture({"running": False, "last": None})
    run.refuse_failed_capture({"running": False,
                               "last": {"state": "done", "seconds": 4.0}})
    run.refuse_failed_capture({"running": False, "last": {"seconds": 4.0}})
    with pytest.raises(HarnessFailure, match="left no .xplane.pb"):
        run.refuse_failed_capture({"running": False, "last": {
            "state": "error",
            "error": "stop_trace returned and left no .xplane.pb"}})


def test_a_capture_without_a_device_event_gives_no_line(tmp_path,
                                                        monkeypatch):
    """On the chip a traced run whose capture holds host planes alone ends
    before the reference and the verdict, with the planes it did find; the
    CPU, which has no device plane, goes on (``test_bench_rehearsal.py``)."""
    import jax

    from benchmark import run
    from benchmark.lib.stages import HarnessFailure

    host_only = """
planes {
  name: "/host:CPU"
  lines { name: "python" events { metadata_id: 1 offset_ps: 0
                                  duration_ps: 5000000000 } }
  event_metadata { key: 1 value { id: 1 name: "dm.recv_wait" } }
}
"""
    folder = tmp_path / "profile" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(host_only))
    monkeypatch.setattr(run, "reference_scores", lambda *a, **k: pytest.fail(
        "the reference ran for a run that has nothing to report"))
    measured = dict.fromkeys(("pool", "records", "gen", "offsets", "seed",
                              "t", "obs", "device", "scorer"))
    measured.update(cell={"config": {}, "traffic": {}}, work=str(tmp_path),
                    trace=True, platform="tpu")
    with pytest.raises(HarnessFailure, match="holds no event") as failure:
        run.conclude(measured)
    assert "/host:CPU" in str(failure.value)
