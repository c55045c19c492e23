"""End-to-end metrics are taken over every sample of the window."""
import numpy as np
import pytest

from benchmarks.chip import harness

MS = 1_000_000


def _req(times_ms):
    r = harness.Served(0, np.zeros(4, np.int32), len(times_ms))
    r.times = [int(t * MS) for t in times_ms]
    r.tokens = [1] * len(times_ms)
    return r


def test_percentile_is_exact_over_all_samples():
    vals = list(range(1, 101))
    assert harness.percentile(vals, 95) == pytest.approx(
        float(np.percentile(vals, 95)))
    assert harness.percentile([], 95) is None


def _stalled(stalls_at_ms):
    """Four requests decoding in step every 10 ms; each stall holds every
    one of them 100 ms (a slow tick of the whole batch)."""
    base = np.arange(0.0, 2000.0, 10.0)
    t = base.copy()
    for at in stalls_at_ms:
        t[base > at] += 100.0
    return [_req(t) for _ in range(4)]


def test_a_stall_inside_the_window_moves_itl_p95():
    window = (0, 1000 * MS)
    base = harness.percentile(harness.inter_token_ms(_stalled([]), *window),
                              95)
    assert base == pytest.approx(10.0)
    inside = _stalled([100, 200, 300, 400, 500, 600])   # 6% of the gaps
    gaps = harness.inter_token_ms(inside, *window)
    assert harness.percentile(gaps, 95) > base
    assert max(gaps) == pytest.approx(110.0)
    # the same stalls after the window leave its samples alone
    after = _stalled([1100, 1200, 1300, 1400, 1500, 1600])
    assert harness.percentile(harness.inter_token_ms(after, *window),
                              95) == pytest.approx(base)


def test_rate_counts_every_token_in_the_window():
    reqs = [_req([5, 15, 25]), _req([950, 1005])]
    assert harness.tokens_in(reqs, 0, 1000 * MS) == 4


def test_end_to_end_metrics_by_name():
    reqs = [_req([100, 110, 130]), _req([400, 500])]
    t1 = 1000 * MS

    def e2e(name):
        return harness.end_to_end(name, reqs, 0, t1, 12.5, 2 ** 31)
    assert e2e("out_tok_s") == pytest.approx(5.0)
    assert e2e("setup_s") == 12.5 and e2e("hbm_peak_gib") == 2.0
    assert e2e("itl_ms_p50") == pytest.approx(20.0)
    for unknown in ("goodput", "ttft_ms_p90"):
        with pytest.raises(KeyError):
            e2e(unknown)


class _OneAdmissionPerStep:
    """A scheduler that admits one queued request a step; every request
    admitted on an earlier step is served a token a step."""

    def __init__(self):
        self.queue, self.live, self.steps = [], [], 0

    def submit(self, prompt, max_new_tokens, on_token):
        self.queue.append([max_new_tokens, on_token])

    def step(self):
        self.steps += 1
        for r in list(self.live):
            r[0] -= 1
            r[1](1, r[0] == 0)
            if r[0] == 0:
                self.live.remove(r)
        if self.queue:
            self.live.append(self.queue.pop(0))


def test_the_window_opens_with_every_slot_decoding():
    import dataclasses
    from pathlib import Path

    from benchmarks.chip import spec
    cell = spec.load_cell(Path(__file__).resolve().parents[3],
                          "mixtral.single-decode")
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, clients=8))
    sched = _OneAdmissionPerStep()
    loop = harness.ClosedLoop(sched, cell, 1000, 2 ** 40 + 3)
    loop.fill()
    clients = cell.traffic["clients"]
    assert clients > 1 and sched.steps == clients + 1
    assert all(r.times for r in loop.served[:clients])
    assert len(sched.live) == clients
