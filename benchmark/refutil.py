"""What the plain references share: Arrow columns as numpy arrays, a
unique-key lookup, bytes of the columns a query reads.  numpy and
pyarrow only; nothing of the program."""

import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)


def days(table_or_date, name=None):
    """A date32 column as int32 days since 1970, or one date as days."""
    if name is None:
        return np.int32((table_or_date - _EPOCH).days)
    col = table_or_date.column(name).combine_chunks()
    return col.cast("int32").to_numpy(zero_copy_only=False)


def f(table, name, dtype=np.float64):
    """A double column, in ``dtype`` (float32 is the control's)."""
    return table.column(name).to_numpy().astype(dtype, copy=False)


def strings(table, name):
    """A string column as a numpy array of fixed-width strings:
    dictionary-encode, then take from the (small) dictionary."""
    d = table.column(name).combine_chunks().dictionary_encode()
    words = np.asarray(d.dictionary.to_pylist(), dtype=str)
    return words[d.indices.to_numpy(zero_copy_only=False)]


def lookup(keys, probe):
    """Position in ``keys`` (unique) of every ``probe`` value, -1 where
    absent: the inner join against a primary key."""
    order = np.argsort(keys, kind="stable")
    sorted_keys = keys[order]
    at = np.searchsorted(sorted_keys, probe)
    at = np.minimum(at, len(keys) - 1)
    return np.where(sorted_keys[at] == probe, order[at], -1)


def column_bytes(tables, need) -> int:
    """Arrow bytes of the columns a query has to read once: 8 a double
    or long, 4 a date, a string its bytes and offsets."""
    return sum(tables[rel].column(c).nbytes
               for rel, cols in need.items() for c in cols)
