"""A semantics a test plugs in by name: static_gang's world, load and
reference, a generator that, with `"toy_fault": true` in the
configuration, leaves out each app's last executor, and one check of its
own, `apps_short_of_executors`, that counts the apps left short."""

import collections

from semantics import static_gang

build_world = static_gang.build_world


def make_generator(world, config, traffic, served, annotate):
    gen = static_gang.make_generator(world, config, traffic, served, annotate)
    if config.get("toy_fault"):
        gen.count -= 1
    return gen


def replay(world, log, bodies):
    out = static_gang.replay(world, log, bodies)
    sent = collections.Counter(ev[1] for ev in log if ev[0] == "executor")
    admitted = [ev[1] for ev in log if ev[0] == "driver" and ev[2] is not None]
    # The window may close inside the last app's executors.
    short = sum(sent[app] < world.executors for app in admitted[:-1])
    out["checks"] = {"apps_short_of_executors": short}
    return out
