"""lib/loadgen.py: reproducible from a seed, the same work for every seed,
absolute due times, lateness reported."""

import json
import os
import threading
import time

import numpy as np

from lib import loadgen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _traffic():
    with open(os.path.join(BENCH, "traffic", "chat_open_loop.json")) as f:
        return json.load(f)


def test_same_seed_same_schedule():
    a = loadgen.make_schedule(_traffic(), 50257, 2**31 + 77, 20)
    b = loadgen.make_schedule(_traffic(), 50257, 2**31 + 77, 20)
    assert len(a) == len(b) == round(_traffic()["rate_rps"] * 20)
    for x, y in zip(a, b):
        assert x["due_s"] == y["due_s"]
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert np.array_equal(x["prompt"], y["prompt"])


def test_every_seed_has_the_same_work():
    t = _traffic()
    a = loadgen.make_schedule(t, 50257, 1, 20)
    b = loadgen.make_schedule(t, 50257, 2, 20)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert sorted(r["max_new_tokens"] for r in a) == \
        sorted(r["max_new_tokens"] for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= t["prompt_len"]["min"]
    assert max(lens) <= t["prompt_len"]["max"]
    assert all(1 <= r["prompt"].min() and r["prompt"].max() < 50257
               for r in a)
    gaps_a = np.sort(np.diff([r["due_s"] for r in a]))
    gaps_b = np.sort(np.diff([r["due_s"] for r in b]))
    assert np.allclose(gaps_a[5:], gaps_b[5:], atol=0.05)


def test_due_times_are_absolute_and_inside_the_window():
    s = loadgen.make_schedule(_traffic(), 50257, 3, 20)
    due = [r["due_s"] for r in s]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 20


def test_open_loop_keeps_the_schedule_and_reports_lateness():
    """A submit that stalls must not shift later due times: the third
    request is sent late by the stall and says so."""
    now = [0.0]
    done_after = {}

    def clock():
        return now[0]

    def sleep(dt):
        # only the submitting thread moves the fake clock; the collector
        # thread really waits
        if threading.current_thread() is threading.main_thread():
            now[0] += max(dt, 1e-4)
        time.sleep(1e-4)

    def submit(req):
        if req["id"] == 0:
            now[0] += 0.35                       # a slow submit
        done_after[req["id"]] = now[0] + 0.1
        return req["id"]

    schedule = [{"due_s": 0.1 * i, "id": i} for i in range(5)]
    t0, recs = loadgen.run_open_loop(
        schedule, submit, lambda h: now[0] >= done_after[h], clock=clock,
        sleep=sleep, grace_s=5.0)
    assert [round(r["due"] - t0, 6) for r in recs] == \
        [0.0, 0.1, 0.2, 0.3, 0.4]
    late = [r["submitted"] - r["due"] for r in recs]
    assert late[1] > 0.2 and late[2] > 0.1 and late[4] < 0.01
    med, worst = loadgen.lateness(recs)
    assert worst == max(late)
    assert all(r["done_at"] is not None and r["error"] is None for r in recs)


def test_refused_and_unanswered_requests_are_failures():
    now = [0.0]

    def submit(req):
        if req["id"] == 1:
            raise RuntimeError("queue full")
        return req["id"]

    def sleep(dt):
        if threading.current_thread() is threading.main_thread():
            now[0] += max(dt, 1e-3)
        time.sleep(1e-4)

    schedule = [{"due_s": 0.0, "id": 0}, {"due_s": 0.01, "id": 1},
                {"due_s": 0.02, "id": 2}]
    _, recs = loadgen.run_open_loop(
        schedule, submit, lambda h: h == 0, clock=lambda: now[0],
        sleep=sleep, grace_s=0.5)
    assert recs[0]["error"] is None and recs[0]["done_at"] is not None
    assert "queue full" in recs[1]["error"]
    assert recs[2]["done_at"] is None and "no answer" in recs[2]["error"]
