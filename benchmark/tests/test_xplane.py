"""The trace reduction: busy union, family sums, idle share, per device."""

import pytest

from harness import xplane

S = 1e9  # the events are in nanoseconds


def _events():
    """Two devices. Device 0: two programs with a 5 s gap between them
    and a 1 s gap inside the second; device 1: one program."""
    return {
        0: {"XLA Modules": [("jit__pallas_align_chain(111)", 0, 10 * S),
                            ("jit__refine_loop_packed(222)", 15 * S, 6 * S)],
            "XLA Ops": [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0, 6 * S),
                        ("%custom-call.2 = u8[4]{0} custom-call()", 6 * S,
                         4 * S),
                        # a while spans the operations of its body
                        ("%while.9 = (s32[]) while(%tuple)", 15 * S, 6 * S),
                        ("%fusion.3 = s32[] fusion()", 15 * S, 2 * S),
                        ("%fusion.3 = s32[] fusion()", 18 * S, 3 * S)],
            "Steps": [("0", 0, 21 * S)]},
        1: {"XLA Modules": [("jit__pallas_align_chain(333)", 2 * S, 4 * S)],
            "XLA Ops": [("%custom-call.2 = u8[4]{0} custom-call()", 2 * S,
                         4 * S)]},
    }


def test_busy_is_the_union_of_the_operation_intervals():
    red = xplane.reduce_events(_events(), chips=2)
    assert red["busy_s_per_device"] == {"0": 16.0, "1": 4.0}
    assert red["busy_s"] == pytest.approx(10.0)
    # a chip the cell uses that shows no event counts as idle
    assert xplane.reduce_events({0: _events()[0]}, chips=4)["busy_s"] == \
        pytest.approx(16.0 / 4)


def test_family_sums_by_program_name_without_the_fingerprint():
    red = xplane.reduce_events(_events(), chips=2)
    assert red["modules"] == {"jit__pallas_align_chain": 14.0,
                              "jit__refine_loop_packed": 6.0}
    assert xplane.family_seconds(red["modules"],
                                 ["_pallas_align_chain", "_attach_bp"]) == 14.0
    assert xplane.family_seconds(red["modules"], ["_refine_loop"]) == 6.0
    assert xplane.family_seconds(red["modules"], ["nothing"]) is None


def test_top_operations_by_self_time_under_short_names():
    red = xplane.reduce_events(_events(), chips=2)
    ops = dict(red["device_ops"])
    assert ops == {"jit__pallas_align_chain/custom-call.2": 8.0,
                   "jit__pallas_align_chain/fusion.1": 6.0,
                   "jit__refine_loop_packed/fusion.3": 5.0,
                   # the while's 6 s less the 5 s of its body
                   "jit__refine_loop_packed/while.9": 1.0}
    assert red["device_ops"][0][1] == 8.0
    assert sum(ops.values()) == pytest.approx(16.0 + 4.0)   # = busy


def test_gaps_are_named_for_the_program_they_wait_for_or_lie_in():
    events = {0: dict(_events()[0])}
    # without the while, its body leaves a 1 s hole inside the program
    events[0]["XLA Ops"] = [ev for ev in events[0]["XLA Ops"]
                            if not ev[0].startswith("%while")]
    red = xplane.reduce_events(events, chips=1)
    assert red["idle_gaps"] == [["before:jit__refine_loop_packed", 5.0],
                                ["inside:jit__refine_loop_packed", 1.0]]
    assert xplane.short_names(events)[0]["XLA Ops"][0][0] == "fusion.1"


def test_idle_share_follows_from_busy_and_window():
    red = xplane.reduce_events({0: _events()[0]}, chips=1)
    window_s = 25.0
    assert 1 - red["busy_s"] / window_s == pytest.approx(0.36)


def test_a_trace_without_device_operations_reduces_to_nothing():
    assert xplane.reduce_events({}, chips=1) == {}
    assert xplane.reduce_events({0: {"Steps": [("0", 0, S)]}}, chips=1) == {}


def test_extract_reads_a_profile_written_here(tmp_path):
    """A CPU trace has no ``/device:TPU`` plane: extraction finds the
    file, reads it with jax alone, and returns no device."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    assert xplane.extract(path) == {}
    with pytest.raises(FileNotFoundError):
        xplane.find_xplane(str(tmp_path / "nothing-here"))


# ---- against the trace recorded on the chip (data/trace_events.json)

def _recorded():
    import json
    import os

    from bench_paths import DATA
    with open(os.path.join(DATA, "trace_events.json")) as fh:
        devices = json.load(fh)["devices"]
    return {int(dev): {line: [tuple(ev) for ev in evs]
                       for line, evs in lines.items()}
            for dev, lines in devices.items()}


def _brute_force_busy_ns(op_events):
    """The union by marking: every 10 us slot an operation touches."""
    slot = 10_000
    touched = set()
    for _, s, d in op_events:
        touched.update(range(int(s) // slot, int(s + d) // slot + 1))
    return len(touched) * slot


def test_recorded_trace_busy_union_family_sums_and_idle_share():
    events = _recorded()
    red = xplane.reduce_events(events, chips=1)
    ops = events[0]["XLA Ops"]
    # 881 operations over 3.7 s of the aligner's phase
    assert len(ops) == 881 and len(events[0]["XLA Modules"]) == 62
    assert red["busy_s"] == pytest.approx(2.364282588, abs=1e-9)
    # the brute-force union rounds every piece outwards to 10 us slots
    assert _brute_force_busy_ns(ops) / 1e9 == pytest.approx(
        red["busy_s"], abs=881 * 2 * 10e-6)
    assert red["busy_s"] <= sum(d for _, _, d in ops) / 1e9
    mods = red["modules"]
    assert mods["jit__pallas_align_chain"] == pytest.approx(1.070745348)
    assert mods["jit__build_rows_packed2"] == pytest.approx(0.952093208)
    align = xplane.family_seconds(mods, [
        "^jit__pallas_align_chain$", "^jit__build_rows_packed2$",
        "^jit__breaking_points_kernel$"])
    assert align == pytest.approx(2.035818341)
    assert align == pytest.approx(sum(
        d for n, _, d in events[0]["XLA Modules"]
        if n.split("(")[0] in ("jit__pallas_align_chain",
                               "jit__build_rows_packed2",
                               "jit__breaking_points_kernel")) / 1e9)
    assert xplane.family_seconds(mods, ["^jit__gather_qpw_rows$"]) == \
        pytest.approx(0.328356459)
    # the excerpt spans 3.7 s: the device idles a third of it, nearly
    # all of that waiting for the host to hand it the next chunk's rows
    assert 1 - red["busy_s"] / 3.7 == pytest.approx(0.361, abs=0.001)
    assert red["idle_gaps"][0][0] == "before:jit__build_rows_packed2"
    assert red["idle_gaps"][0][1] == pytest.approx(1.110030292)
    assert red["device_ops"][0] == [
        "jit__pallas_align_chain/pallas_nw_fwd.1",
        pytest.approx(0.732221884)]
    # self times add up to the busy time: nothing counted twice
    assert sum(xplane._self_times(ops)[i][2] for i in range(len(ops))) \
        / 1e9 == pytest.approx(red["busy_s"], rel=1e-6)
