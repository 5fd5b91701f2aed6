"""Device Huffman decode probe's share of its roofline over the window's decodes (%)."""
from bench import readers


def read(ctx):
    return readers.kernel_roofline(ctx, "decode", "huffman_probe")
