"""The one general traffic generator: closed-loop clients walking decks
that a traffic file describes, in an order the seed draws.

A traffic file (``traffic/<mix>.json``) has

``statements``: id -> ``template`` (statements/<template>.sql and
  references/<template>.py), ``catalog``, ``class`` and ``variants`` (a
  small pool of parameter sets: a new literal is a new program, so
  every variant is warmed in set-up);
``clients``: groups of ``count`` clients with a ``deck`` of statement
  ids; ``variants`` "all" puts every variant of every id into the deck,
  "one_per_run" lets the seed pick one per id for the whole run;
  ``order`` "shuffle" reshuffles the deck for every pass, "cycle" keeps
  the file's order and lets the seed pick where it starts;
``stop``: "statement" begins no statement after the window has closed,
  "pass" finishes every pass that began inside it;
``traced_seconds``, ``traced_offset_seconds``: how long a traced run
  records the device, and how far into its window the recording begins
  (0 where left out).

Every seed gives the same multiset of statements per pass, in another
order, so the seed does not change the work.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple


@dataclasses.dataclass
class Sample:
    """One statement a client sent. Times are time.perf_counter()."""

    client: int
    statement: object            # manifest.Statement
    t_submit: float
    t_done: float
    query_id: str = ""
    state: str = ""
    error: Optional[str] = None
    columns: Optional[list] = None
    rows: Optional[list] = None
    metrics_after: Optional[Dict[str, float]] = None
    query_info: Optional[Dict] = None
    wrong: str = ""              # set by the comparison

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit

    @property
    def ok(self) -> bool:
        return self.error is None and not self.wrong


@dataclasses.dataclass
class ClientPlan:
    index: int
    deck: List          # of manifest.Statement: one pass, in file order
    order: str
    seed: int

    def passes(self) -> Iterator[List]:
        rng = random.Random(f"{self.seed}:client:{self.index}")
        if self.order == "cycle":
            k = rng.randrange(len(self.deck))
            deck = self.deck[k:] + self.deck[:k]
            while True:
                yield list(deck)
        elif self.order == "shuffle":
            while True:
                deck = list(self.deck)
                rng.shuffle(deck)
                yield deck
        else:
            raise ValueError(f"unknown order {self.order!r}")


def plan_clients(cell, seed: int) -> List[ClientPlan]:
    plans = []
    for group in cell.traffic["clients"]:
        deck = []
        for sid in group["deck"]:
            variants = cell.statements[sid]
            if group["variants"] == "all":
                deck.extend(variants)
            elif group["variants"] == "one_per_run":
                deck.append(random.Random(
                    f"{seed}:variant:{sid}").choice(variants))
            else:
                raise ValueError(
                    f"unknown variants {group['variants']!r}")
        for _ in range(int(group["count"])):
            plans.append(ClientPlan(len(plans), deck, group["order"],
                                    seed))
    return plans


def statements_used(plans: List[ClientPlan]) -> List:
    """Every distinct statement some client may send, in a fixed order."""
    seen: Dict[str, object] = {}
    for plan in plans:
        for st in plan.deck:
            seen.setdefault(st.key, st)
    return [seen[k] for k in sorted(seen)]


def run_window(served, plans: List[ClientPlan], seconds: float,
               stop: str, scrape: bool = False,
               on_statement: Optional[Callable[[Sample], None]] = None,
               ) -> Tuple[float, List[Sample]]:
    """Closed loop, no think time: every client sends its next statement
    when the last one's final row is decoded. Returns (window start,
    samples). ``scrape`` reads /metrics after each statement (traced
    runs only: it delays the client's next statement)."""
    if stop not in ("statement", "pass"):
        raise ValueError(f"unknown stop {stop!r}")
    samples: List[Sample] = []
    lock = threading.Lock()
    go = threading.Event()
    deadline = [0.0]

    def loop(plan: ClientPlan):
        clients = {}
        go.wait()
        for deck in plan.passes():
            if time.perf_counter() >= deadline[0]:
                return
            for st in deck:
                if stop == "statement" and \
                        time.perf_counter() >= deadline[0]:
                    return
                client = clients.get(st.catalog)
                if client is None:
                    client = clients[st.catalog] = served.client(
                        st.catalog)
                t0 = time.perf_counter()
                try:
                    result = client.execute(st.sql)
                    s = Sample(plan.index, st, t0, time.perf_counter(),
                               result.query_id, result.state,
                               columns=result.columns, rows=result.rows)
                    if result.error or result.state != "FINISHED":
                        s.error = f"{result.state}: {result.error}"
                except Exception as e:  # noqa: BLE001 - a failed
                    # statement is counted, and the client goes on
                    s = Sample(plan.index, st, t0, time.perf_counter(),
                               error=f"{type(e).__name__}: {e}")
                if scrape:
                    s.metrics_after = served.metrics()
                with lock:
                    samples.append(s)
                if on_statement is not None:
                    on_statement(s)

    threads = [threading.Thread(target=loop, args=(p,), daemon=True)
               for p in plans]
    for t in threads:
        t.start()
    t_start = time.perf_counter()
    deadline[0] = t_start + seconds
    go.set()
    for t in threads:
        t.join()
    return t_start, samples
