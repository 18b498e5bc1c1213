"""``device_idle_pct`` where the end-to-end metrics are those of the served
streams (server.throughput)."""

from readers import device_idle_pct as read  # noqa: F401
