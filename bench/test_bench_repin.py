"""``repin_share``'s reader on synthetic runs: the requests of the
window's ``repro.repin`` spans over the requests picked; 0 where the
program's collects leave spans and none was re-pinned; nothing where they
leave none (a program without the re-pin) or the run is untraced."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness, spans  # noqa: E402


def _run(count, lanes, picked=8):
    run = harness.Run("g500-sharded.hot", 1.0, 1.0,
                      counters={"picked": picked})
    run.spans = spans.SpanReduced({}, count, lanes, None, None, None, None,
                                  [])
    return run


@pytest.mark.parametrize("count,lanes,picked,want", [
    ({"collect": 5, "repin": 2}, {"repin": 3.0}, 8, 37.5),
    ({"collect": 5}, {}, 8, 0.0),
    ({"dispatch": 4}, {}, 8, None),
    ({"collect": 5, "repin": 2}, {"repin": 3.0}, 0, None),
])
def test_repin_share_reads_the_repin_spans(count, lanes, picked, want):
    read = harness.load_reader("repin_share.sharded")
    got = read(_run(count, lanes, picked))
    assert got == (None if want is None else pytest.approx(want))


def test_repin_share_untraced_reads_nothing():
    run = _run({}, {})
    run.spans = None
    assert harness.load_reader("repin_share.sharded")(run) is None
