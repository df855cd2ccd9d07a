"""Padding rows over bucket rows of the window's batches, in percent, from
the program's ``batch_pack`` spans (rows packed, bucket capacity)."""


def read(run):
    spans = run.window.spans
    cap = sum(s.attrs["bucket"] for s in spans)
    if not cap:
        return None
    return 100.0 * (1.0 - sum(s.attrs["rows"] for s in spans) / cap)
