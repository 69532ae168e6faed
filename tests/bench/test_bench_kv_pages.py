"""The reader of the paged decode kernel's reach: the live pages of the
decoded rows over their block tables' width, None where the count is 0 or
the program has no such counter."""
import pytest

from bench import metrics

NAME = "decode.kv_live_page_share"


def window(stats):
    return metrics.Window(
        seconds=10.0, stats=stats, drops={}, commits=[], episodes=[],
        compiles=0, trace=None, ctx_sum=0, row_steps=0, shape={}, rank=16,
        peaks=None, kind="TPU v5 lite")


@pytest.mark.parametrize("live,table,want", [(130, 1000, 0.13),
                                             (1000, 1000, 1.0)])
def test_share_over_the_window(live, table, want):
    w = window({"kv_live_pages": live, "kv_table_pages": table})
    assert metrics.reader(NAME)(w) == pytest.approx(want)


@pytest.mark.parametrize("stats", [
    {"kv_live_pages": 0, "kv_table_pages": 0},
    {"decode_steps": 200, "sampled_tokens": 6000},   # a program without it
])
def test_none_where_nothing_was_counted(stats):
    assert metrics.reader(NAME)(window(stats)) is None
