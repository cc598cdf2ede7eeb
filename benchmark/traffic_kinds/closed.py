"""Closed loop: `clients` callers, each sending its next request as soon as
the previous one has ended. Parameters (traffic/<mix>.json):

  clients    a number, or "slots" = the configuration's lanes / mesh slots
  lead_in_s  the clients' first requests are staggered evenly over it
  pool, prompt_len, output_len   see traffic.size_pool

`plan` is pure: the same (mix, slots, seed) gives the same plan. `run`
drives it through the harness's `ctx` until it is cancelled."""

from __future__ import annotations

import asyncio
import itertools
import random

EPOCHS = 4


def plan(mix: dict, pool, slots: int, seed: int) -> dict:
    clients = slots if mix["clients"] == "slots" else int(mix["clients"])
    rng = random.Random(seed)
    lead_in = float(mix["lead_in_s"])
    order = list(range(clients))
    rng.shuffle(order)
    # some epochs of the pool, each shuffled anew, dealt round-robin; a
    # client that reaches the end of its list starts it again
    per_client = [[] for _ in range(clients)]
    k = 0
    for _epoch in range(EPOCHS):
        sizes = list(pool)
        rng.shuffle(sizes)
        for size in sizes:
            per_client[k % clients].append(size)
            k += 1
    return {
        "clients": clients,
        "starts": [lead_in * order[c] / clients for c in range(clients)],
        "requests": per_client,
    }


async def run(plan_: dict, ctx) -> None:
    async def client(c: int):
        await ctx.sleep_until(plan_["starts"][c])
        await ctx.may_start(c)
        due = max(ctx.now(), plan_["starts"][c])
        for i, (n_prompt, n_out) in enumerate(itertools.cycle(plan_["requests"][c])):
            await ctx.request(c, i, n_prompt, n_out, due)
            due = ctx.now()  # closed loop: due the moment the last one ended

    tasks = [asyncio.create_task(client(c)) for c in range(plan_["clients"])]
    try:
        await asyncio.gather(*tasks)
    finally:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
