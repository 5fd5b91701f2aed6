"""Device Huffman pack kernel's share of its roofline over the window's ingests (%)."""
from bench import readers


def read(ctx):
    return readers.kernel_roofline(ctx, "ingest", "huffman_pack")
