"""A closed loop's topic pool: blocks of ``pool`` topics, as many as the
traffic's ``rate_ceiling_qps`` needs, the first the single pool of a
traffic without a ceiling, bit for bit.  A loop that answers faster than
``pool / seconds`` runs on into the later blocks; past the ceiling it
raises and names the ceiling."""
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import datagen, harness as H, loadgen, spec  # noqa: E402
from rehearse import load_cell  # noqa: E402
# imported by the loop's first send: here, so that no window pays for it
import repro.serve.request  # noqa: E402,F401

CLOSED = "rerank.title.closed"
#: a vocabulary of the rehearsal's size, renamed by a permutation
R2T = np.random.default_rng(7).permutation(12000).astype(np.int32)


def _query():
    return load_cell(CLOSED).traffic["query"]


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_block_zero_is_the_single_pool_bit_for_bit(seed):
    one = datagen.topics(_query(), 500, seed, R2T)
    many = datagen.topic_blocks(_query(), 500, 3, seed, R2T)
    assert list(many) == list(one)
    for k, v in one.items():
        assert many[k].dtype == v.dtype
        assert np.array_equal(many[k][:500], v)
    assert np.array_equal(datagen.topic_blocks(_query(), 500, 1, seed,
                                               R2T)["terms"], one["terms"])


def test_qids_are_unique_and_consecutive_across_blocks():
    Q = datagen.topic_blocks(_query(), 200, 4, 11, R2T)
    assert np.array_equal(Q["qid"], np.arange(800))
    assert len(Q["terms"]) == len(Q["weights"]) == 800


def test_blocks_differ_and_each_topic_has_distinct_terms():
    Q = datagen.topic_blocks(_query(), 300, 3, 11, R2T)
    blocks = Q["terms"].reshape(3, 300, -1)
    for a in range(3):
        for b in range(a + 1, 3):
            assert not np.array_equal(blocks[a], blocks[b])
    for row, w in zip(Q["terms"], Q["weights"]):
        live = row[row >= 0]
        assert 2 <= len(live) <= 4
        assert len(set(live.tolist())) == len(live)
        assert np.all(w[:len(live)] == 1.0) and np.all(w[len(live):] == 0)


def test_block_count_follows_the_ceiling():
    traffic = load_cell(CLOSED).traffic
    seconds = float(spec.benchmark()["run_seconds"])
    # ceil((64 + 5000 q/s x 51 s) / 20000) = 13 blocks at 51 s
    assert H.pool_blocks(traffic, seconds) == int(np.ceil(
        (traffic["clients"] + traffic["rate_ceiling_qps"] * seconds)
        / traffic["pool"]))
    assert H.pool_blocks({"clients": 64, "pool": 20000,
                          "rate_ceiling_qps": 5000}, 51.0) == 13
    assert H.pool_blocks({"clients": 4, "pool": 16,
                          "rate_ceiling_qps": 100}, 1.0) == 7
    no_ceiling = {k: v for k, v in traffic.items()
                  if k != "rate_ceiling_qps"}
    assert H.pool_blocks(no_ceiling, seconds) == 1
    tr = H.make_traffic(no_ceiling, seconds, 5, R2T)
    one = datagen.topics(traffic["query"], traffic["pool"], 5, R2T)
    for k, v in one.items():
        assert np.array_equal(tr.Q[k], v)


class InstantServer:
    """Answers each request as it is submitted, ``delay_s`` after it."""

    def __init__(self, delay_s: float):
        self.delay_s = delay_s
        self.qids = []

    def submit_one(self, row, timeout_ms=None):
        time.sleep(self.delay_s)
        self.qids.append(int(row["qid"][0]))
        req = SimpleNamespace(
            done=threading.Event(), error=None,
            result={"docids": np.zeros((1, 10), np.int32),
                    "scores": np.zeros((1, 10), np.float32)},
            trace=SimpleNamespace(t_done=time.monotonic(), timed_out=False,
                                  queue_wait_ms=0.0, service_ms=0.0,
                                  batch_size=1, bucket=1, stage_ms={}))
        req.done.set()
        return req


def _run(ceiling: float, seconds: float = 0.5):
    traffic = {"mode": "closed", "clients": 2, "pool": 16,
               "rate_ceiling_qps": ceiling, "query": _query()}
    tr = H.make_traffic(traffic, seconds, 2 ** 31 + 5, R2T)
    server = InstantServer(delay_s=0.001)      # at most 1000 q/s
    win = H.drive(SimpleNamespace(server=server), traffic, tr, seconds,
                  loadgen.Annotator(False))
    return traffic, tr, server, win


def test_a_loop_faster_than_its_first_block_runs_on():
    traffic, tr, server, win = _run(ceiling=4000.0)
    n_pool = len(tr.Q["qid"])
    assert n_pool == 16 * H.pool_blocks(traffic, 0.5) > 16
    recs, attempted, failed = H.served_requests(win)
    assert attempted > traffic["pool"] and failed == 0
    assert len(recs) == attempted == len(server.qids)
    assert server.qids == list(range(attempted))


def test_a_loop_past_its_ceiling_fails_and_says_why():
    with pytest.raises(RuntimeError, match=r"all 16 topics of its pool at "
                       r"[0-9.]+ q/s, drawn for a 20\.0 q/s rate ceiling; "
                       r"raise the traffic's rate_ceiling_qps"):
        _run(ceiling=20.0)
