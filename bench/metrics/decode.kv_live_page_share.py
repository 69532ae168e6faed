"""Kernels: the share of the block table the paged decode kernel has to
read, as the live pages of the rows decoded over their tables' width
(Δkv_live_pages ÷ Δkv_table_pages). A property of the traffic, counted on
the host: it bounds what visiting only the live pages can save, and is not
a measurement of the kernel. None where the program counts no pages."""


def read(w):
    n = w.stats.get("kv_table_pages", 0)
    return w.stats["kv_live_pages"] / n if n else None
