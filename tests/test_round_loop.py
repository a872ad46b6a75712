"""The one host round loop (``dopt/engine/loop.py``), driven by a fake
engine whose hooks append to a list: no model, no device program.

What the six real paths do with it is held bit for bit by
tests/test_round_anatomy.py, test_prefetch.py, test_obs.py,
test_fused_chaos.py, test_population.py, test_serve.py and
test_faults.py; these cases pin the skeleton itself."""

import contextlib
import pathlib
import re
import threading

import numpy as np
import pytest

from dopt.engine import loop
from dopt.engine.loop import HostLoop, RoundPath
from dopt.utils.profiling import PhaseTimers

ENGINE_DIR = pathlib.Path(loop.__file__).parent


class FakeEngine(HostLoop):
    """Twenty lines of engine: a round counter, a log, and the five
    hooks.  ``fail`` names the hook that raises in the second block."""

    def __init__(self, fail=None):
        self.log, self.open, self.builders = [], [], []
        self.timers = PhaseTimers(tracer=self)
        self.round, self.history, self.telemetry = 0, [], None
        self.fail = fail

    @contextlib.contextmanager
    def span(self, name):  # the PhaseTimers tracer hook
        self.log.append(name)
        self.open.append(name)
        try:
            yield
        finally:
            self.open.pop()

    def _save(self, path):
        self.log.append(("save", self.round))

    def _hook(self, name, ts):
        if self.fail == name and ts[0] > 0:
            raise RuntimeError(f"{name} failed")
        self.log.append((name, list(ts)))

    def path(self, block=1, prefetch=False):
        def draw(ts):
            self.log.append(("draw", list(ts),
                             "staged" if "round_step" in self.open
                             else "inline"))
            return list(ts)

        def build(ts):
            self.builders.append((ts[0], threading.current_thread().name))
            return ts

        def launch(ts):
            self._hook("launch", ts)

            def fn():
                self._hook("fn", ts)
                return "carried state", np.zeros(len(ts))

            return "fake_fn", fn, (), {}

        def commit(out):
            self.log.append("commit")
            return out[-1]

        def record(ts, packed):
            assert isinstance(packed, np.ndarray) and len(packed) == len(ts)
            self._hook("record", ts)
            self.round += len(ts)

        return RoundPath(draw=draw, build=build, launch=launch,
                         commit=commit, record=record, block=block,
                         prefetch=prefetch)


@pytest.mark.parametrize("prefetch", [False, True])
@pytest.mark.parametrize("k", [1, 3])
def test_block_order(k, prefetch):
    """plan → dispatch → stage-next → wait → commit → fetch → record,
    twice: the second block is taken from the stager when there is
    one, planned inline when not."""
    eng = FakeEngine()
    eng._run_loop(eng.path(block=k, prefetch=prefetch), rounds=2 * k)
    b0, b1 = list(range(k)), list(range(k, 2 * k))

    def block(ts, *, plan, stage):
        return ([*(["host_batch_plan", ("draw", ts, "inline")]
                   if plan else []),
                 ("launch", ts), "round_step", "round_dispatch",
                 ("fn", ts),
                 *(["host_batch_plan", ("draw", stage, "staged")]
                   if stage else []),
                 "round_wait", "commit", "round_fetch", "round_record",
                 ("record", ts)])

    assert eng.log == (block(b0, plan=True, stage=b1 if prefetch else None)
                       + block(b1, plan=not prefetch, stage=None))
    assert eng.round == 2 * k
    # The pure build runs inline without a stager, on the stager's
    # thread with one.
    main = threading.current_thread().name
    assert eng.builders == [(0, main),
                            (k, "dopt-prefetch" if prefetch else main)]
    # Every span closed, and the spans of a block nest under nothing
    # but round_step.
    assert eng.open == []
    assert eng.timers.counts["round_step"] == 2
    # Inline plans, plus the staged block's draw and its timed build.
    assert eng.timers.counts["host_batch_plan"] == 2 + prefetch


@pytest.mark.parametrize("k,every,rounds,saves,staged", [
    (1, 2, 6, [2, 4, 6], [[1], [3], [5]]),
    (2, 4, 8, [4, 8], [[2, 3], [6, 7]]),
])
def test_checkpoint_boundaries(k, every, rounds, saves, staged, tmp_path):
    """``save`` runs at every scheduled boundary, per-round and blocked
    alike, and nothing is staged across one: the block after a
    checkpoint is drawn inline, from committed state."""
    eng = FakeEngine()
    eng._run_loop(eng.path(block=k, prefetch=True), rounds,
                  checkpoint_every=every, checkpoint_path=tmp_path / "ck")
    assert [e[1] for e in eng.log
            if isinstance(e, tuple) and e[0] == "save"] == saves
    draws = [e for e in eng.log if isinstance(e, tuple) and e[0] == "draw"]
    assert [d[1] for d in draws if d[2] == "staged"] == staged
    assert all(d[1][0] % every == 0 for d in draws if d[2] == "inline")
    # The checkpoint span opens inside the block's step, after its
    # record.
    i = eng.log.index(("save", saves[0]))
    assert eng.log[i - 1] == "checkpoint"
    assert eng.log[i - 2][0] == "record"


@pytest.mark.parametrize("fail", ["launch", "fn", "record"])
def test_failure_discards_the_stager(fail, monkeypatch):
    """An exception in a hook discards the stager (its background
    build is joined, nothing stays pending) and leaves ``round`` at the
    last committed value."""
    stagers = []

    class Spy(loop.PrefetchStager):
        def __init__(self):
            super().__init__()
            self.discards = 0
            stagers.append(self)

        def discard(self):
            self.discards += 1
            super().discard()

    monkeypatch.setattr(loop, "PrefetchStager", Spy)
    eng = FakeEngine(fail=fail)
    with pytest.raises(RuntimeError, match=f"{fail} failed"):
        eng._run_loop(eng.path(block=2, prefetch=True), rounds=6)
    (stager,) = stagers
    assert stager.discards >= 1 and len(stager) == 0
    assert not any(t.name == "dopt-prefetch" for t in threading.enumerate())
    assert eng.round == 2
    assert eng.open == []
    assert not hasattr(eng, "total_time")


# The inline plan and the staged draw both open host_batch_plan.
SPAN_SITES = {"host_batch_plan": 2, "round_step": 1, "round_dispatch": 1,
              "round_wait": 1, "round_fetch": 1, "round_record": 1,
              "checkpoint": 1}


@pytest.mark.parametrize(
    "pattern,sites",
    [*((rf"""phase\(["']{s}["']\)""", n) for s, n in SPAN_SITES.items()),
     (r"timers\.step\(", 1), (r"PrefetchStager\(\)", 1),
     *((rf"def {f}\(", 1) for f in (
         "_device_telemetry", "_consensus_value", "_run_summary_telemetry",
         "_round_telemetry", "run_served"))])
def test_one_site_under_engine(pattern, sites):
    """Each host span, the step annotation, the stager and the shared
    helpers exist in ``loop.py`` and in no other file under
    ``dopt/engine/`` (``seqlm.py``, a step loop of its own, aside)."""
    sources = {p.name: p.read_text() for p in ENGINE_DIR.glob("*.py")
               if p.name != "seqlm.py"}
    hits = {name: len(re.findall(pattern, text))
            for name, text in sources.items()}
    assert {name: n for name, n in hits.items() if n} == {"loop.py": sites}
    if pattern.startswith("phase"):
        # Nor is the span opened under its name in any other form (a
        # ``measure`` call, a name held in a variable).
        literal = pattern.removeprefix("phase\\(").removesuffix("\\)")
        assert [name for name, text in sources.items()
                if re.search(literal, text)] == ["loop.py"]
