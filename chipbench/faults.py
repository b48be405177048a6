"""Faults planted beneath the served path, to show that ``correct`` comes
out false when the timed path breaks. Each takes the ``GlobalServer``
before warm-up. The benchmark's own runs plant none; ``run.py --fault``
reads one on the chip at the cell's own size, and the tests in a CPU
rehearsal."""

from __future__ import annotations


def token_altered(srv) -> None:
    """Every token the engine samples is shifted by one where it is
    produced."""
    for p in srv.pipelines:
        model = p.engine.model
        greedy = model.sample_greedy

        def shifted(logits, _greedy=greedy, _v=model.cfg.vocab):
            return (_greedy(logits) + 1) % _v
        model.sample_greedy = shifted


def state_unchanged(srv) -> None:
    """Every decode step returns the KV cache it was given: nothing is
    written and no position advances."""
    for p in srv.pipelines:
        model = p.engine.model
        step = model.decode_step

        def frozen(params, cache, tokens, _step=step):
            logits, _ = _step(params, cache, tokens)
            return logits, dict(cache)
        model.decode_step = frozen


def handover_dropped(srv) -> None:
    """The exchange between chips is left out: the requests a reclaim
    takes off its replica are never handed to the replicas that survive
    it."""
    interrupt = srv.interrupt_instance

    def dropped(instance_id, _interrupt=interrupt):
        moved = _interrupt(instance_id)
        ids = {id(r) for r in moved}
        for p in srv.pipelines:
            p.queue[:] = [r for r in p.queue if id(r) not in ids]
        return moved
    srv.interrupt_instance = dropped


FAULTS = {"token_altered": token_altered, "state_unchanged": state_unchanged,
          "handover_dropped": handover_dropped}
