"""build_s: seconds to build the run (data, partition, pool, evaluator and
the contact plan), by the benchmark's clock around the building."""


def read(ctx):
    return ctx["build_s"]
