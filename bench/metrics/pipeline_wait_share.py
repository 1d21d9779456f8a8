"""Share of the queries' service time that the panel pipeline's consumer
waited for a panel (``pipeline.consumer_wait_seconds``)."""


def read(rec):
    service = sum(end - start for _, start, end in rec.queries)
    if service <= 0:
        return None
    return 100.0 * rec.registry.get("pipeline.consumer_wait_seconds", 0.0) / service
