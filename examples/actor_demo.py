"""Actor runtime demo (ref: ``byzpy/examples/actor_demo/actor_demo.py:1-40``).

Spawns a counter actor on the thread backend, calls it over async RPC,
and passes messages through a named channel.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import asyncio

from byzpy_tpu.engine.actor.base import spawn_actor
from byzpy_tpu.engine.actor.factory import resolve_backend


class Counter:
    def __init__(self, start=0):
        self.value = start

    def add(self, k):
        self.value += k
        return self.value

    def get(self):
        return self.value


async def main():
    backend = resolve_backend("thread")
    ref = await spawn_actor(backend, Counter, 10)

    print("add(5) ->", await ref.add(5))
    print("add(2) ->", await ref.add(2))
    print("get()  ->", await ref.get())

    # named channels: a mailbox on the actor anyone can post to
    await backend.chan_open("inbox")
    await backend.chan_put("inbox", {"hello": "world"})
    print("chan_get ->", await backend.chan_get("inbox"))

    await backend.close()


if __name__ == "__main__":
    asyncio.run(main())
