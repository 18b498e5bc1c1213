"""``compiles_in_window`` where the end-to-end metric is ``query_s``
(session.q1, session.q14)."""

from readers import compiles_in_window as read  # noqa: F401
