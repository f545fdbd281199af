"""A whole run, with the timed path broken underneath, comes out not
correct: one test per fault each cell can have.  The check for a chip is
off; everything else is a normal run of the harness at tiny sizes."""
import numpy as np
import pytest

RELOC = ["reloc_ycsb_zipf"]
SERVE = ["serve_azure_conv"]


@pytest.mark.parametrize("name", RELOC + SERVE)
def test_sound_run_is_correct(run_tiny, name):
    r = run_tiny(name, trace=True)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert r["metrics"]


@pytest.mark.parametrize("name", RELOC + SERVE)
def test_control_run_is_not_correct(run_tiny, name):
    """``--control 1``: the reference one precision down in the
    program's place.  The relocation limits are the configuration's own
    (0: relocation is exact); the serving limit is the tiny model's
    (``conftest.tiny_cell``), as the 1.5B limit means nothing at these
    widths."""
    r = run_tiny(name, seconds=1.0, control=True)
    assert not r["correct"], r["checks"]
    assert all(v["value"] <= v["limit"]
               for v in r["program_checks"].values()), r["program_checks"]


def test_fused_codec_path_is_correct(run_tiny, monkeypatch):
    """The chip takes the fused Pallas codec; here it runs interpreted."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_BACKEND", "pallas_interpret")
    r = run_tiny("reloc_ycsb_zipf", seconds=0.5)
    assert r["correct"], r["checks"]


# -- relocation -------------------------------------------------------------
def _unchanged_state(monkeypatch):
    from repro.core import CollectiveMoveManager

    sync = CollectiveMoveManager.sync

    def no_moves(self):
        self._range_moves = []
        return sync(self)

    monkeypatch.setattr(CollectiveMoveManager, "sync", no_moves)


def _half_the_moves(monkeypatch):
    from repro.core import CollectiveMoveManager

    register = CollectiveMoveManager.register_range_move
    seen = []

    def every_other(self, col, r, dest):
        seen.append(r)
        if len(seen) % 2:
            register(self, col, r, dest)

    monkeypatch.setattr(CollectiveMoveManager, "register_range_move",
                        every_other)


def _no_exchange(monkeypatch, min_width=0):
    """Each place receives its own send buffer back: no all_to_all (only
    for row classes of ``min_width`` bytes and wider)."""
    from repro.core.transport import DeviceTransport

    composite, fused = DeviceTransport._exchange_fn, \
        DeviceTransport._fused_exchange_fn

    def keep(buf, *_):
        return buf

    monkeypatch.setattr(DeviceTransport, "_exchange_fn",
                        lambda self, n, S, W: keep if W >= min_width
                        else composite(self, n, S, W))
    monkeypatch.setattr(DeviceTransport, "_fused_exchange_fn",
                        lambda self, n, S, W: keep if W >= min_width
                        else fused(self, n, S, W))


def _altered_row(monkeypatch):
    from repro.core import DistArray

    decode = DistArray.decode_rows

    def flipped(self, rows, manifest):
        r, arr = decode(self, rows, manifest)
        if len(arr):
            arr = arr.copy()
            arr.view(np.uint32)[0, 0] ^= 1
        return r, arr

    monkeypatch.setattr(DistArray, "decode_rows", flipped)


@pytest.mark.parametrize("name", RELOC)
@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_moves,
                                   _no_exchange, _altered_row])
def test_reloc_fault_is_caught(run_tiny, monkeypatch, name, fault):
    fault(monkeypatch)
    r = run_tiny(name, seconds=0.5)
    assert not r["correct"]


# -- serving ----------------------------------------------------------------
def _step_keeps_state(monkeypatch):
    from repro.models import transformer as T

    step = T.decode_step

    def stale(params, cfg, par, state, token_ids, **kw):
        _, logits = step(params, cfg, par, state, token_ids, **kw)
        return state, logits

    monkeypatch.setattr(T, "decode_step", stale)


def _half_the_batch(monkeypatch):
    from repro.serving import DecodeEngine

    decode = DecodeEngine.decode_batch

    def half(self, seq_kvs, *, work=1):
        return decode(self, seq_kvs[:max(len(seq_kvs) // 2, 1)], work=work)

    monkeypatch.setattr(DecodeEngine, "decode_batch", half)


def _no_kv_exchange(monkeypatch):
    # KV rows of the tiny model are the only rows of 4 KiB and wider;
    # sequence metadata still crosses, so the run completes
    _no_exchange(monkeypatch, min_width=4096)


def _altered_token(monkeypatch):
    from repro.serving import DecodeEngine

    decode = DecodeEngine.decode_batch

    def bumped(self, seq_kvs, *, work=1):
        dt = decode(self, seq_kvs, work=work)
        if seq_kvs:
            seq_kvs[0].token = (seq_kvs[0].token + 1) % self.cfg.vocab_size
        return dt

    monkeypatch.setattr(DecodeEngine, "decode_batch", bumped)


@pytest.mark.parametrize("name", SERVE)
@pytest.mark.parametrize("fault", [_step_keeps_state, _half_the_batch,
                                   _altered_token, _no_kv_exchange])
def test_serve_fault_is_caught(run_tiny, monkeypatch, name, fault):
    fault(monkeypatch)
    r = run_tiny(name, seconds=1.0)
    assert not r["correct"]
