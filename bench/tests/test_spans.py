"""Idle time by the program's spans (``bench/spans.py``), on hand-made
events, on a CPU trace the profiler writes, and on recorded TPU windows."""
import threading
from pathlib import Path

import pytest

from bench import spans
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
MAIN, WORKER = "python3#0", "python3#1"
#: recorded traced windows with the program's spans: the first 0.1 s of a
#: ``bench/span_window.py --save`` window (``span_window.window_events``)
RECORDED = ["tpu_v5e_pallas_spans.events.json.gz",
            "tpu_v5e_2x2_replay_spans.events.json.gz"]


def ev(plane, line, name, a, b, args=None):
    return spans.Event(plane, line, name, a, b, args)


def hand_trace():
    return [
        ev(HOST, MAIN, "bench:window", 0.0, 10.0),
        ev(HOST, MAIN, "repro:step", 0.0, 9.0),
        ev(HOST, MAIN, "repro:region:Amul", 1.0, 6.0, {"op": 0}),
        ev(HOST, MAIN, "repro:dispatch", 1.0, 2.0, {"op": 0}),
        ev(HOST, MAIN, "repro:sync", 2.0, 5.0, {"op": 0}),
        ev(HOST, MAIN, "repro:readback", 6.5, 8.0),
        # the worker's wait overlaps idle time but attributes none of it
        ev(HOST, WORKER, "repro:sync", 5.0, 10.0, {"op": 1}),
        # device 0 busy 2-4: idle 0-2, 4-10; device 1 busy 0-10
        ev(D0, "XLA Ops", "fusion.1", 2.0, 4.0),
        ev(D1, "XLA Ops", "fusion.1", 0.0, 10.0),
    ]


def test_timeline_takes_the_innermost_span():
    pieces = spans.timeline([e for e in hand_trace() if e.line == MAIN
                             and e.name.startswith("repro:")])
    assert pieces == [(0.0, 1.0, "step"), (1.0, 2.0, "dispatch"),
                      (2.0, 5.0, "sync"), (5.0, 6.0, "region"),
                      (6.0, 6.5, "step"), (6.5, 8.0, "readback"),
                      (8.0, 9.0, "step")]


def test_a_gap_splits_across_spans_by_time():
    idle = spans.idle_by_span(hand_trace())
    # device 0: 0-1 step, 1-2 dispatch, 4-5 sync, 5-6 region, 6-6.5 step,
    # 6.5-8 readback, 8-9 step, 9-10 under no span; device 1 never idles
    assert idle["dispatch"] == pytest.approx(1.0 / 2)
    assert idle["sync"] == pytest.approx(1.0 / 2)
    assert idle["region"] == pytest.approx(1.0 / 2)
    assert idle["readback"] == pytest.approx(1.5 / 2)
    assert idle["step"] == pytest.approx(2.5 / 2)
    assert idle[spans.UNATTRIBUTED] == pytest.approx(1.0 / 2)
    assert sum(idle.values()) == pytest.approx(tr.reduce(hand_trace())
                                               .idle_s)
    assert spans.coverage(idle) == pytest.approx(4.5 / 8.0)


def test_worker_spans_attribute_nothing():
    events = [e for e in hand_trace() if e.line != WORKER]
    assert spans.idle_by_span(events) == spans.idle_by_span(hand_trace())
    # with the window opened on the worker's line, only its span counts
    moved = [ev(HOST, WORKER, e.name, e.start, e.end)
             if e.name == "bench:window" else e for e in hand_trace()]
    idle = spans.idle_by_span(moved)
    assert set(idle) == {"sync", spans.UNATTRIBUTED}
    assert idle["sync"] == pytest.approx(5.0 / 2)


def test_idle_under_no_span_is_unattributed():
    events = [e for e in hand_trace() if not e.name.startswith("repro:")]
    idle = spans.idle_by_span(events)
    assert idle == {spans.UNATTRIBUTED: pytest.approx(4.0)}
    assert spans.coverage(idle) == 0.0


def test_ops_are_read_across_threads():
    assert [e.line for e in spans.by_op(hand_trace(), 1)] == [WORKER]
    assert len(spans.by_op(hand_trace(), 0)) == 3


def test_saved_rows_of_five_and_six_fields_load(tmp_path):
    old = spans.read_saved(str(DATA / "tpu_v5e_pallas_window.events.json.gz"))
    assert old and all(e.args is None for e in old)
    assert tr.reduce(old).idle_s == pytest.approx(
        tr.reduce(tr.read_saved(
            str(DATA / "tpu_v5e_pallas_window.events.json.gz"))).idle_s)
    # the old recordings have no program spans: all their idle is
    # unattributed
    assert set(spans.idle_by_span(old)) == {spans.UNATTRIBUTED}
    path = tmp_path / "six.json.gz"
    spans.save(hand_trace(), str(path))
    assert spans.read_saved(str(path)) == hand_trace()


def test_load_keeps_stats_and_threads_apart(tmp_path):
    """A CPU trace: two threads of one name stay two lines, and stats
    reach ``args``."""
    import jax
    from repro.core.ledger import span

    def worker():
        with span("sync", op=7):
            pass

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with span("region", "Amul", op=7, impl="ref"):
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=60)
    jax.profiler.stop_trace()
    assert not t.is_alive()
    events = spans.load(str(tmp_path))
    mine = spans.by_op(events, 7)
    assert {e.name for e in mine} == {"repro:region:Amul", "repro:sync"}
    region = next(e for e in mine if e.name == "repro:region:Amul")
    assert region.args == {"op": 7, "impl": "ref"}
    assert len({e.line for e in mine}) == 2
    assert spans.dispatch_line(events) == (region.plane, region.line)


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_window_is_put_down_to_program_spans(name):
    """A recorded TPU window: at least 90% of the devices' idle time lies
    under a program span finer than a SIMPLE iteration, and the split
    adds up to the idle time ``bench.trace`` reads."""
    events = spans.read_saved(str(DATA / name))
    idle = spans.idle_by_span(events)
    assert sum(idle.values()) == pytest.approx(tr.reduce(events).idle_s)
    assert spans.coverage(idle) >= 0.9
    assert idle.get("sync", 0.0) > 0 and idle.get("dispatch", 0.0) > 0
