"""``pump_host_ms`` where the end-to-end metric is ``scan_query_s``
(session.q6)."""

from book_readers import pump_host_ms as read  # noqa: F401
