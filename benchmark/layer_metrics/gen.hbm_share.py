"""Share, in percent, of the chips' HBM bandwidth that decoding has
to use: the bytes decode must stream (``arith.decode_bytes``: each
replica's bf16 weights once a token step, each live sequence's
key/value prefix) over the generation MFC's blocked wall, the chips and
the chip's peak bandwidth. Wall-clock, with prefill and sampling
inside: not the decode kernel's roofline share."""


def read(record):
    wall = record["medians"].get("gen")
    if wall is None or "decode_bytes" not in record["work"]:
        return None
    peak = record["chips"] * record["peaks"]["hbm_bw"]
    return 100.0 * record["work"]["decode_bytes"] / (wall * peak)
