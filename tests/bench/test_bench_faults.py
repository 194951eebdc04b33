"""A run whose timed path is broken underneath comes out not correct:
once for each fault the cells can have (an answer or a token altered
where it is produced; a decode step that returns its state unchanged).
The look for a chip is skipped; the rest of the run is the harness's."""

import numpy as np

from bench import run


def test_mapper_answer_altered_is_not_correct(tiny, monkeypatch):
    from repro.runtime import service

    submit = service.KernelService.submit

    def shifted(self, requests):
        out = submit(self, requests)
        for res in out:
            res.pos += 1000         # beyond the accuracy tolerance
        return out

    monkeypatch.setattr(service.KernelService, "submit", shifted)
    config, mix = tiny("map-ont")
    out = run.execute("map-ont", 11, 2.0, False, config=config, mix=mix)
    assert not out["correct"]
    for name in ("pos_mismatches", "misplaced_share"):
        assert out["checks"][name]["value"] > out["checks"][name]["limit"]


def test_lm_token_altered_is_not_correct(tiny, monkeypatch):
    from repro.serve import scheduler

    step = scheduler.Scheduler.step

    def altered(self):
        done = step(self)
        for c in done:
            c.tokens = c.tokens.copy()
            c.tokens[-1] = (c.tokens[-1] + 1) % self.cfg.vocab
        return done

    monkeypatch.setattr(scheduler.Scheduler, "step", altered)
    config, mix = tiny("rwkv-docs")
    out = run.execute("rwkv-docs", 12, 2.0, False, config=config, mix=mix)
    assert not out["correct"]


def test_lm_decode_state_unchanged_is_not_correct(tiny, monkeypatch,
                                                  fresh_programs):
    from repro.models import ssm

    decode = ssm.rwkv_time_mix_decode

    def frozen(params, cfg, x, state):
        y, _ = decode(params, cfg, x, state)
        return y, state

    monkeypatch.setattr(ssm, "rwkv_time_mix_decode", frozen)
    config, mix = tiny("rwkv-docs")
    out = run.execute("rwkv-docs", 13, 2.0, False, config=config, mix=mix)
    assert not out["correct"]
    assert out["checks"]["logit_gap_max"]["value"] > \
        out["checks"]["logit_gap_max"]["limit"]
    assert np.isfinite(out["checks"]["logit_gap_max"]["value"])
