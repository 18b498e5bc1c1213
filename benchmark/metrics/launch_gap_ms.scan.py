"""``launch_gap_ms`` where the end-to-end metric is ``scan_query_s``
(session.q6)."""

from readers import launch_gap_ms as read  # noqa: F401
