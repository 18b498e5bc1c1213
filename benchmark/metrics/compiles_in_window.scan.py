"""``compiles_in_window`` where the end-to-end metric is ``scan_query_s``
(session.q6)."""

from readers import compiles_in_window as read  # noqa: F401
