"""Window arithmetic on retire times: pure functions, no jax, no clock.

A "retire" is the moment an update's outputs exist on the host side
(the in-flight window's ``block_until_ready`` returned).  Everything the
end-to-end rates are made of is computed here from a list of retire
times, so the tests can feed synthetic lists.
"""

import statistics
from typing import List, Optional, Sequence

BLOCK_UPDATES = 8          # updates per block of the block-median rate
DRAIN_LOOKBACK = 8         # the warm-up ends on the waits of this many updates
DRAIN_REAL = 4             # ...of which at least this many were real waits
REAL_WAIT_S = 0.010        # a ready ``get`` takes microseconds
MAX_WARMUP_UPDATES = 64    # open anyway: the loop is learner-bound


def backlog_drained(waits_s: Sequence[float],
                    lookback: int = DRAIN_LOOKBACK,
                    real: int = DRAIN_REAL,
                    real_wait_s: float = REAL_WAIT_S) -> bool:
    """True once the learner had to wait for its batch on at least
    ``real`` of the last ``lookback`` updates: the queue that filled
    while the first update compiled is gone and the loop runs at its
    own pace.  Not on every one of them: the actor groups run in step,
    so their batches arrive together and the learner's waits alternate
    between a real one and a ready ``get`` (chip runs, PR 23)."""
    if len(waits_s) < lookback:
        return False
    return sum(w >= real_wait_s for w in waits_s[-lookback:]) >= real


def window_rate(retires_s: Sequence[float],
                frames_per_update: float) -> Optional[float]:
    """Completion to completion: the clock runs from the first retire
    of the window to the last, and the frames are those of the updates
    that retired after the first."""
    if len(retires_s) < 2:
        return None
    span = retires_s[-1] - retires_s[0]
    if span <= 0:
        return None
    return (len(retires_s) - 1) * frames_per_update / span


def block_rates(retires_s: Sequence[float], frames_per_update: float,
                block: int = BLOCK_UPDATES) -> List[float]:
    """Rates of consecutive blocks of ``block`` updates.  Block i runs
    from retire ``i*block`` to retire ``(i+1)*block``; a tail shorter
    than a block is left out."""
    rates = []
    i = 0
    while i + block < len(retires_s):
        span = retires_s[i + block] - retires_s[i]
        if span > 0:
            rates.append(block * frames_per_update / span)
        i += block
    return rates


def block_median_rate(retires_s: Sequence[float],
                      frames_per_update: float,
                      block: int = BLOCK_UPDATES) -> Optional[float]:
    rates = block_rates(retires_s, frames_per_update, block)
    if not rates:
        return window_rate(retires_s, frames_per_update)
    return statistics.median(rates)


def intervals_ms(retires_s: Sequence[float]) -> List[float]:
    return [(b - a) * 1e3 for a, b in zip(retires_s, retires_s[1:])]


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in [0, 100]); None on no samples."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(min(len(ordered), rank)) - 1]


def spread(values: Sequence[float]) -> Optional[float]:
    """Interquartile distance over the median, as the driver takes it
    (``statistics.quantiles(values, n=4)``)."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


class WindowClock:
    """The warm-up / window state machine both probes drive.

    ``on_retire(t, wait_s)`` is called once per retired update, in
    order.  While warming up, updates are discarded until ``ready``
    says the loop is at its own pace; the retire that follows opens the
    window (its time is the window's first edge).  The first retire at
    or past ``seconds`` after that edge closes it and is left out.
    """

    def __init__(self, seconds: float, min_warmup: int,
                 needs_drain: bool,
                 max_warmup: int = MAX_WARMUP_UPDATES):
        self.seconds = float(seconds)
        self.min_warmup = int(min_warmup)
        self.needs_drain = needs_drain
        self.max_warmup = int(max_warmup)
        self.waits: List[float] = []
        self.discarded = 0
        self.drained = not needs_drain
        self.retires: List[float] = []     # inside the window
        self.closed = False
        # One more condition the probe may set: e.g. "the program's
        # first log-interval publish is behind us" (it compiles a few
        # tiny programs the first time).
        self.gate = lambda: True

    def _ready(self) -> bool:
        if self.discarded < self.min_warmup or not self.gate():
            return False
        if not self.needs_drain:
            return True
        if backlog_drained(self.waits):
            self.drained = True
            return True
        return self.discarded >= self.max_warmup

    def on_retire(self, t: float, wait_s: Optional[float] = None) -> str:
        """Returns 'warmup', 'opened', 'inside' or 'closed'."""
        if self.closed:
            return "closed"
        if wait_s is not None:
            self.waits.append(wait_s)
        if not self.retires:
            if self._ready():
                self.retires.append(t)
                return "opened"
            self.discarded += 1
            return "warmup"
        if t - self.retires[0] >= self.seconds:
            self.closed = True
            return "closed"
        self.retires.append(t)
        return "inside"
