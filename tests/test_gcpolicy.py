"""The collector policy of server processes (`utils/gcpolicy.py`): full
passes freeze their survivors, the whole heap is walked again only after
it has doubled and a minute has passed. Driven by hand against a fake
collector and clock: no real collection, no wall-clock wait."""

import gc
import types

import pytest

from nomad_tpu.utils import gcpolicy


class FakeGC:
    def __init__(self):
        self.frozen, self.young, self.calls = 0, 0, []

    def freeze(self):
        self.frozen += self.young
        self.young = 0
        self.calls.append("freeze")

    def unfreeze(self):
        self.young += self.frozen
        self.frozen = 0
        self.calls.append("unfreeze")

    def get_freeze_count(self):
        return self.frozen


@pytest.fixture
def policy(monkeypatch):
    fake, clock = FakeGC(), types.SimpleNamespace(now=100.0)
    monkeypatch.setattr(gcpolicy, "gc", fake)
    monkeypatch.setattr(gcpolicy, "time",
                        types.SimpleNamespace(perf_counter=lambda: clock.now))
    monkeypatch.setattr(gcpolicy, "_state", dict(
        gcpolicy._state, whole_heap=0, whole_at=0.0, thawed=True, t0=0.0))
    monkeypatch.setattr(gcpolicy, "STATS", dict.fromkeys(gcpolicy.STATS, 0))

    def full_pass(made: int, took: float = 0.0, at: float = None):
        if at is not None:
            clock.now = at
        fake.young += made
        gcpolicy._on_collection("start", {"generation": 2})
        clock.now += took
        gcpolicy._on_collection("stop", {"generation": 2})
        return fake

    return full_pass, fake


def test_a_full_pass_freezes_its_survivors(policy):
    full_pass, fake = policy
    full_pass(1000, took=0.25)
    assert fake.frozen == 1000 and fake.young == 0
    assert gcpolicy.STATS["full_passes"] == 1
    assert gcpolicy.STATS["whole_heap_passes"] == 1
    assert gcpolicy.STATS["full_pass_s"] == pytest.approx(0.25)
    assert gcpolicy.STATS["longest_s"] == pytest.approx(0.25)
    # the passes after it walk only what was made since
    full_pass(100)
    assert fake.calls == ["freeze", "freeze"] and fake.frozen == 1100
    assert gcpolicy.STATS["whole_heap_passes"] == 1


@pytest.mark.parametrize("made, later, thaws", [
    (999, 3600.0, False),     # not doubled, however long ago
    (1000, 59.0, False),      # doubled, but the last whole pass is young
    (1000, 60.0, True),       # doubled and a minute old
    (5000, 61.0, True),
])
def test_the_whole_heap_is_walked_again_after_a_doubling_and_a_minute(
        policy, made, later, thaws):
    full_pass, fake = policy
    full_pass(1000, at=100.0)
    full_pass(made, at=100.0 + later)
    assert (fake.calls[-1] == "unfreeze") is thaws
    if thaws:
        # everything is young again: the next pass walks it, counts as a
        # whole-heap pass and freezes what it left
        assert fake.frozen == 0 and fake.young == 1000 + made
        full_pass(10)
        assert gcpolicy.STATS["whole_heap_passes"] == 2
        assert fake.frozen == 1010 + made
        assert gcpolicy._state["whole_heap"] == 1010 + made


def test_young_generations_are_left_alone(policy):
    _full_pass, fake = policy
    for generation in (0, 1):
        gcpolicy._on_collection("start", {"generation": generation})
        gcpolicy._on_collection("stop", {"generation": generation})
    assert fake.calls == [] and gcpolicy.STATS["full_passes"] == 0


def test_install_is_idempotent_and_works_on_the_real_collector(monkeypatch):
    before = list(gc.callbacks)
    # the policy as a process starts with it. An earlier test of this
    # worker that built an agent leaves it installed with a whole-heap
    # pass on record: once that is a minute old and the heap has
    # doubled, the pass below is the one that thaws, and nothing is
    # frozen after it
    monkeypatch.setattr(gcpolicy, "_state", dict(
        gcpolicy._state, whole_heap=0, whole_at=0.0, thawed=True, t0=0.0))
    monkeypatch.setattr(gcpolicy, "STATS", dict.fromkeys(gcpolicy.STATS, 0))
    auto = gc.isenabled()
    try:
        gcpolicy.install()
        gcpolicy.install()
        assert gc.callbacks.count(gcpolicy._on_collection) == 1
        # no automatic pass, on this thread or another, between the
        # reading and the pass this test makes
        gc.disable()
        passes = gcpolicy.STATS["full_passes"]
        keep = [[i] for i in range(1000)]
        gc.collect()
        assert gcpolicy.STATS["full_passes"] == passes + 1
        assert gcpolicy.STATS["whole_heap_passes"] == 1
        assert gc.get_freeze_count() >= len(keep)
    finally:
        if auto:
            gc.enable()
        gc.unfreeze()
        gc.callbacks[:] = before
