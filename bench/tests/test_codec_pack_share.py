"""The pack kernel's aligned share reads the program's ``codec.pack``
spans, and nothing where there are none.  Nothing here is a device
number."""
import pytest

from bench import harness


def _obs(spans):
    return harness.Observed({}, {}, 2.0, {"rounds": 4}, spans, None, {})


def test_reads_the_share_over_the_windows_spans():
    spans = [{"name": "codec.pack", "dur": 10.0,
              "args": {"rows_aligned": 1, "rows_rotated": 3}},
             {"name": "codec.pack", "dur": 12.0,
              "args": {"rows_aligned": 2, "rows_rotated": 0}},
             {"name": "codec.pack", "dur": 5.0},   # device tables: no count
             {"name": "transport.exchange", "dur": 40.0,
              "args": {"rows": 9}}]
    read = harness.load_reader("codec.pack_aligned_share")
    assert read(_obs(spans)) == pytest.approx(50.0)


@pytest.mark.parametrize("spans", [
    [],
    [{"name": "transport.exchange", "dur": 40.0}],
    [{"name": "codec.pack", "dur": 5.0}],
    [{"name": "codec.pack", "dur": 5.0,
      "args": {"rows_aligned": 0, "rows_rotated": 0}}],
], ids=["no_spans", "no_pack_span", "uncounted", "no_live_rows"])
def test_reads_nothing_without_counted_rows(spans):
    read = harness.load_reader("codec.pack_aligned_share")
    assert read(_obs(spans)) is None
