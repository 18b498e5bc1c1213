"""``books_unaccounted_pct`` where the end-to-end metric is ``scan_query_s``
(session.q6)."""

from book_readers import books_unaccounted_pct as read  # noqa: F401
