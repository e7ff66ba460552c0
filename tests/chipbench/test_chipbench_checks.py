"""The comparison that decides ``correct`` fails what it must: the control
(the plain reference in the session's place, one precision down) and the
faults a checkpointing session can have, each planted under a run that is
otherwise whole."""
import pytest

import chipbench_testkit as kit


@pytest.fixture(autouse=True)
def _kernels_on(monkeypatch):
    for gate in kit.KERNEL_GATES:
        monkeypatch.setenv(gate, "1")


@pytest.mark.parametrize("name", kit.CELLS)
def test_control_is_not_correct(name):
    from chipbench.control import ControlSession

    out, _ = kit.run_tiny(name, make_session=lambda d, cmds, trace:
                          ControlSession(d, cmds, trace=trace))
    assert out["correct"] is False
    assert out["checks"]["checkouts_differing"]["value"] >= 1
    assert out["checks"]["readbacks_differing"]["value"] >= 1


@pytest.mark.parametrize("name", kit.CELLS)
def test_a_checkout_that_leaves_the_state_unchanged_is_caught(name,
                                                              monkeypatch):
    from repro.core.session import KishuSession

    monkeypatch.setattr(KishuSession, "checkout", lambda self, c: None)
    out, _ = kit.run_tiny(name)
    assert out["correct"] is False
    assert out["checks"]["checkouts_differing"]["value"] >= 1


@pytest.mark.parametrize("name", kit.CELLS)
def test_a_byte_altered_where_a_chunk_is_written_is_caught(name,
                                                           monkeypatch):
    from chipbench import bench
    from repro.core.checkpoint import CheckpointWriter

    put, measure = CheckpointWriter._put, bench.Run._measure
    armed = []

    def altered(self, ck, data):
        if armed:                   # the window's commits only
            data = bytearray(data)
            data[len(data) // 2] ^= 0x40
        put(self, ck, bytes(data))

    def window(self, watch):
        armed.append(True)
        measure(self, watch)

    monkeypatch.setattr(CheckpointWriter, "_put", altered)
    monkeypatch.setattr(bench.Run, "_measure", window)
    out, _ = kit.run_tiny(name)
    assert out["correct"] is False


def test_fingerprint_sees_one_word_and_the_tie():
    import jax.numpy as jnp

    from chipbench import digest

    a = jnp.arange(4096, dtype=jnp.float32).reshape(64, 64)
    ns = {"state/params/embed": a, "state/params/lm_head": a,
          "hparams/lr": 3e-4}
    fp = digest.fingerprint(ns)
    assert fp["<tied>"] is True
    assert digest.fingerprint(dict(ns)) == fp
    b = a.at[17, 3].set(jnp.nextafter(a[17, 3], jnp.inf))
    moved = digest.fingerprint(dict(ns, **{"state/params/embed": b,
                                           "state/params/lm_head": b}))
    assert digest.differences(moved, fp) == ["state/params/embed",
                                             "state/params/lm_head"]
    untied = digest.fingerprint(dict(ns, **{"state/params/lm_head": a + 0}))
    assert digest.differences(untied, fp) == ["<tied>"]
    lr = digest.fingerprint(dict(ns, **{"hparams/lr": 1.5e-4}))
    assert digest.differences(lr, fp) == ["hparams/lr"]
