"""``compiles_in_window`` where the end-to-end metric is ``scan_query_s`` of
the served streams (server.throughput)."""

from readers import compiles_in_window as read  # noqa: F401
