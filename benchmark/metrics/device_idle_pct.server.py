"""``device_idle_pct`` where the end-to-end metric is ``scan_query_s`` of the
served streams (server.throughput)."""

from readers import device_idle_pct as read  # noqa: F401
