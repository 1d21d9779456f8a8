"""Mean seconds per transition in the program's graph build: the harness
span around ``snaps.adjacency`` (features to sharded adjacency), ended by
``block_until_ready`` in the traced run."""


def read(rec):
    spans = rec.span_seconds("bench.graph")
    return sum(spans) / len(spans) if spans else None
