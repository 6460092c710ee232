"""Request columns over padded columns dispatched: each ticket records its
batch's width and its column, so the batches are the tickets in column 0."""


def read(rec):
    return rec.get("serve", {}).get("batch_fill")
