"""The check that decides ``correct``, driven end to end on the CPU at a
tiny size: sound runs pass, and the control and each planted fault fail.

The harness's look for a chip is skipped; everything else is the run's."""

import time

import numpy as np

SEED = 2**31 + 12345


def _serve(root, fault=None, calibrate=False):
    from bench import run as bench_run

    return bench_run.run_cell(root, "tiny.serve", SEED, 1.0, False,
                              t_start=time.perf_counter(),
                              require_chip=False, fault=fault,
                              calibrate=calibrate, cache=False)


def test_serve_sound_run_is_correct_and_control_fails(tiny_root):
    res = _serve(tiny_root, calibrate=True)
    assert res["correct"], res["checks"]
    assert res["notes"]["checked_tokens"] > 0
    limit = res["checks"]["served_gap"]["limit"]
    assert res["notes"]["readings"]["served_gap"]["control"] > limit
    assert list(res)[-1] == "checks"


def test_serve_altered_token_is_caught(tiny_root):
    def fault(eng):
        """The second token of every request is altered where the engine
        emits it."""
        step = eng.step
        seen = {}

        def altered():
            out = []
            for uid, tok in step():
                seen[uid] = seen.get(uid, 0) + 1
                if seen[uid] == 2:
                    tok = (tok + 1) % eng.cfg.vocab_size
                out.append((uid, tok))
            return out
        eng.step = altered

    res = _serve(tiny_root, fault=fault)
    assert not res["correct"], res["checks"]


def test_serve_fp8_control_in_place_of_the_program_is_caught(tiny_root):
    """The control: every token the engine emits is replaced, where it is
    emitted, by the first choice of the fp8 reference on the same
    context (prompt and the tokens served so far)."""
    import jax

    from bench import harness, reference

    cell = harness.load_cell(tiny_root, "tiny.serve")
    arch = reference.Arch.from_config(cell.conf)
    params = reference.init_params(arch, SEED)
    span = int(cell.traffic["max_len"])

    @jax.jit
    def first_choice(tokens, pos):
        h = reference.hidden(params, tokens, arch, precision="fp8")
        return reference.logits(params, h[pos][None], arch, "fp8")[0].argmax()

    def fault(eng):
        add, step = eng.add_request, eng.step
        seqs = {}

        def added(req):
            seqs[req.uid] = list(req.tokens)
            return add(req)

        def control():
            out = []
            for uid, _ in step():
                seq = seqs[uid]
                tokens = np.zeros((span,), np.int32)
                tokens[:len(seq)] = seq
                tok = int(first_choice(tokens, len(seq) - 1))
                seq.append(tok)
                out.append((uid, tok))
            return out
        eng.add_request, eng.step = added, control

    res = _serve(tiny_root, fault=fault)
    assert res["notes"]["checked_tokens"] > 0
    assert not res["correct"], res["checks"]


def test_serve_check_covers_rows_past_the_window(tiny_root):
    """The first wave starts mid-stream, so some decode rows, and some
    checked ones, have keys outside the sliding window."""
    res = _serve(tiny_root)
    assert res["correct"], res["checks"]
    assert res["notes"]["rows_past_window"] > 0
    assert res["notes"]["checked_rows_past_window"] > 0
