"""``books_unaccounted_pct`` where the end-to-end metric is ``query_s``
(session.q1, session.q14)."""

from book_readers import books_unaccounted_pct as read  # noqa: F401
