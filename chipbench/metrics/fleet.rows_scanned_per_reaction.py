"""Reservations walked by the capacity queries (``FleetNode.free_cores``,
``NodePool.next_completion``) per reaction: ``fleet.capacity_rows_scanned``
over ``service.batches``. Grows with every job a node has held, so the
window's reactions slow as it runs. Moves reaction_p50_ms."""


def read(ctx):
    rows, reactions = ctx.counter("fleet.capacity_rows_scanned"), ctx.counter("service.batches")
    if rows is None or not reactions:
        return None
    return rows / reactions
