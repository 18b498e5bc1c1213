"""``queue_wait_ms`` where the end-to-end metrics are those of the served
streams (server.throughput)."""

from readers import queue_wait_ms as read  # noqa: F401
