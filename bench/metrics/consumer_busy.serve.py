"""Share of the window the THAPI consumer thread spent in its ticks (drain,
stream tick, controllers): the ``consumer_drain`` spans' total."""


def read(ctx):
    busy = ctx.get("consumer_drain_s")
    return None if busy is None else 100.0 * busy / ctx["window_s"]
