"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (set-up, window, reference, comparison) at a tiny size on the CPU:
once sound (``correct`` true), and once for each fault the cell can
have.  The limits are the tiny size's own (its sound runs read loss gaps
under 1e-3, leaf norm gaps under 0.05; the faults read 0.015 and more on
the loss or 0.18 and more on a leaf); the chip's are in the cells'
workload files.
"""
import pytest

from conftest import TRAIN_LIMITS, TRAIN_MIX


def run_cell(name, program=None, seconds=0.5):
    import run
    return run.main(["--workload", name, "--seed", str(2**33 + 17),
                     "--seconds", str(seconds)], program=program,
                    chips_check=False)


def unchanged(run):
    """A step that returns its state unchanged."""
    def step(batch):
        old = run.state
        m = run.step(batch)
        run.state = old
        return m
    return step


def half_batch(run):
    """Half of the batch left out, the mean taken over the rest."""
    def step(batch):
        n = len(batch["tokens"]) // 2
        return run.step({k: v[:n] for k, v in batch.items()})
    return step


@pytest.mark.parametrize("config", ["xlstm-tiny", "minicpm-tiny"])
@pytest.mark.parametrize("fault", [None, unchanged, half_batch])
def test_train_fault(tiny, config, fault):
    _, write = tiny
    write("t", config, "tmix", mix=TRAIN_MIX, batch=4, reference_rows=2,
          limits=TRAIN_LIMITS)
    r = run_cell("t", program=fault)
    assert r["correct"] is (fault is None), r["checks"]


def altered_tokens(scheduler_cls):
    """Every generated token altered where it is produced."""
    class Altered(scheduler_cls):
        def _decode_tick(self):
            live = [s for s in self.slots if s.req is not None]
            did = super()._decode_tick()
            for s in live:
                if s.req is not None and s.req.tokens:
                    t = (s.req.tokens[-1] + 1) % self.cfg.vocab_size
                    s.req.tokens[-1] = t
                    s.last_token = t
            return did
    return Altered


SERVE_MIX = {"job": "serve", "rate_per_s": 8.0, "shape_seed": 1,
             "prompt_len": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 32},
             "output_len": {"median": 6, "sigma": 0.5, "lo": 4, "hi": 12},
             "serve_spec": {"max_slots": 4, "page_size": 16, "max_len": 48,
                            "prefill_chunk": 8, "max_queue": 256},
             "trace_seconds": 1, "check_requests": 4, "check_rows": 2,
             "controls": {"fp8": {"prec": "fp8"}}}
SERVE_LIMITS = {"served_logit_gap": 0.1}


@pytest.mark.parametrize("fault", [None, altered_tokens])
def test_serve_fault(tiny, fault):
    _, write = tiny
    write("s", "xlstm-tiny", "smix", mix=SERVE_MIX, limits=SERVE_LIMITS)
    r = run_cell("s", program=fault, seconds=1.0)
    assert r["correct"] is (fault is None), r["checks"]
