"""``queue_wait_ms``: the mean wait for a run slot
(``QueryHandle.queue_wait_s``) of the served streams' requests
(server.throughput)."""

from readers import queue_wait_ms as read  # noqa: F401
