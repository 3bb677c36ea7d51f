"""Mean corpora per executed chunk: corpora summed over the window's
batched chunks (each pack's corpus count) and single-corpus chunks, over
the number of chunks (``ServerStats`` ``batched_calls + single_calls``)."""


def read(record):
    return record.chunk_corpora / record.chunks if record.chunks else None
