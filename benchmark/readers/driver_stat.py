"""A number the driver itself took on its side of the system under test
(how late the generator ran, the acknowledgement time seen by the client).
The driver leaves it in ``ctx.stats``; a missing one returns None."""


def read(metric: dict, ctx) -> float | None:
    return ctx.stats.get(metric["stat"])
