"""The one load generator: open-loop (a schedule of due times) and
closed-loop (a pool of callers), over HTTP, from one process and one
thread. Every seed sends the same multiset of gaps and of user ranks, in
another order and mapped onto other users, so that a seed changes which
users are asked and never how much work is offered.
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field

import aiohttp
import numpy as np

REQUEST_TIMEOUT_S = 30.0
# a closed loop's plan is longer than any rate a server could reach
CLOSED_PLAN_REQUESTS_PER_S = 4000


@dataclass
class Plan:
    """What will be sent: bodies in sending order and, for an open loop,
    when each is due (seconds from the start of the warm-up)."""
    users: list[str]              # user id of each request, in order
    rows: np.ndarray              # factor row of each (-1: unknown user)
    due: np.ndarray | None        # open loop only
    warmup_s: float
    seconds: float
    num: int

    def body(self, i: int) -> bytes:
        return json.dumps({"user": self.users[i], "num": self.num}).encode()


def make_plan(traffic: dict, seed: int, n_users: int, seconds: float, *,
              rate_qps: float | None) -> Plan:
    """`traffic` gives: zipf_exponent, unknown_share, num, warmup_s,
    base_seed; an open loop's rate comes with the cell. The draws come
    from `base_seed`; `seed` only permutes them."""
    warmup_s = float(traffic["warmup_s"])
    total_s = warmup_s + seconds
    base = np.random.default_rng([int(traffic["base_seed"]), 0x10AD])
    perm = np.random.default_rng([seed, 0x10AD])
    if rate_qps is not None:
        n = int(round(rate_qps * total_s))
        gaps = base.exponential(1.0, n - 1)
        gaps *= total_s * (n - 1) / n / gaps.sum()
        due = np.concatenate([[0.0], np.cumsum(perm.permutation(gaps))])
    else:
        # a closed loop sends as fast as answers come back
        n = int(CLOSED_PLAN_REQUESTS_PER_S * total_s)
        due = None
    ranks = _zipf_ranks(base, n, n_users, float(traffic["zipf_exponent"]))
    unknown = base.random(n) < float(traffic["unknown_share"])
    order = perm.permutation(n)
    ranks, unknown = ranks[order], unknown[order]
    # which user holds which popularity rank is the seed's to say; only
    # the ranks that are asked need an id
    asked = np.unique(ranks)
    rows_of = perm.choice(n_users, len(asked), replace=False)
    rows = rows_of[np.searchsorted(asked, ranks)].astype(np.int64)
    rows[unknown] = -1
    users = [f"u{r}" if r >= 0 else f"nobody{i}"
             for i, r in enumerate(rows.tolist())]
    return Plan(users=users, rows=rows, due=due, warmup_s=warmup_s,
                seconds=seconds, num=int(traffic["num"]))


def _zipf_ranks(rng, n: int, n_users: int, exponent: float) -> np.ndarray:
    """n popularity ranks in [0, n_users) with P(rank r) ~ 1/(r+1)^s, by
    inverting the continuous law (exact enough for choosing whom to ask)."""
    u = rng.random(n)
    if abs(exponent - 1.0) < 1e-9:
        r = np.exp(u * np.log(n_users + 1.0)) - 1.0
    else:
        a = 1.0 - exponent
        r = (u * ((n_users + 1.0) ** a - 1.0) + 1.0) ** (1.0 / a) - 1.0
    return np.minimum(r.astype(np.int64), n_users - 1)


@dataclass
class Outcome:
    """What came back, per request of the plan (index-aligned)."""
    sent: np.ndarray       # seconds from start; nan if never sent
    done: np.ndarray       # seconds from start; nan if no answer
    status: np.ndarray     # HTTP status; 0 for an exception or a timeout
    answers: dict[int, list] = field(default_factory=dict)


async def _one(session, url, plan: Plan, i: int, out: Outcome, t0: float
               ) -> None:
    out.sent[i] = time.monotonic() - t0
    try:
        async with session.post(url, data=plan.body(i), headers={
                "Content-Type": "application/json"}) as resp:
            body = await resp.read()
            out.status[i] = resp.status
            if resp.status == 200:
                out.answers[i] = json.loads(body)["itemScores"]
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError, KeyError):
        out.status[i] = 0
    out.done[i] = time.monotonic() - t0


def _session() -> aiohttp.ClientSession:
    return aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=0, ttl_dns_cache=300),
        timeout=aiohttp.ClientTimeout(total=REQUEST_TIMEOUT_S))


def _new_outcome(n: int) -> Outcome:
    return Outcome(sent=np.full(n, np.nan), done=np.full(n, np.nan),
                   status=np.zeros(n, np.int32))


async def run_open_loop(url: str, plan: Plan, *, hooks=()) -> Outcome:
    """Send request i at plan.due[i] whatever the server does. `hooks` are
    (seconds from start, coroutine function) pairs run at their time."""
    out = _new_outcome(len(plan.users))
    async with _session() as session:
        t0 = time.monotonic()
        tasks = [asyncio.create_task(_at(t0, at, fn)) for at, fn in hooks]
        for i, due in enumerate(plan.due.tolist()):
            wait = due - (time.monotonic() - t0)
            if wait > 0:
                await asyncio.sleep(wait)
            tasks.append(asyncio.create_task(
                _one(session, url, plan, i, out, t0)))
        await asyncio.gather(*tasks)
    return out


async def run_closed_loop(url: str, plan: Plan, callers: int, *, hooks=()
                          ) -> Outcome:
    """`callers` callers, each sending its next request when its last
    came back, until warmup_s + seconds have passed; what is in flight
    then is waited for."""
    out = _new_outcome(len(plan.users))
    t_end = plan.warmup_s + plan.seconds
    next_i = 0

    async def caller(session, t0):
        nonlocal next_i
        while time.monotonic() - t0 < t_end and next_i < len(plan.users):
            i = next_i
            next_i += 1
            await _one(session, url, plan, i, out, t0)

    async with _session() as session:
        t0 = time.monotonic()
        tasks = [asyncio.create_task(_at(t0, at, fn)) for at, fn in hooks]
        tasks += [asyncio.create_task(caller(session, t0))
                  for _ in range(callers)]
        await asyncio.gather(*tasks)
    return out


async def _at(t0: float, at: float, fn) -> None:
    wait = at - (time.monotonic() - t0)
    if wait > 0:
        await asyncio.sleep(wait)
    await fn()
