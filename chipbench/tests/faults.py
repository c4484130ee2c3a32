"""Faults planted in the program under the benchmark (for the tests):
each breaks the timed path in one way a later change could."""
import jax
import jax.numpy as jnp


def _unchanged(setattr_):
    from repro.core.fed.api.substrate import QuantumSubstrate
    from repro.core.fed.serve import groups
    setattr_(QuantumSubstrate, "run_round",
             lambda self, state, key, round: (state, {}))
    setattr_(groups, "_serve_tick",
             lambda params, smom, err, *a, **k: (params, smom, err))


def _half_batch(setattr_):
    from repro.core.fed import participation
    orig = participation.round_weights

    def half(schedule, node_sizes, mask):
        w = orig(schedule, node_sizes, mask)
        n = w.shape[0]
        w = w * (jnp.arange(n) < n // 2).astype(w.dtype)
        return w / jnp.sum(w)
    setattr_(participation, "round_weights", half)


def _answer_altered(setattr_):
    from repro.core.fed.api.substrate import QuantumSubstrate
    from repro.core.fed.serve import groups
    evaluate = QuantumSubstrate.evaluate

    def shifted(self, state):
        out = evaluate(self, state)
        out["test_fidelity"] += 1e-2
        return out
    setattr_(QuantumSubstrate, "evaluate", shifted)
    sync_out = groups.StackedGroup.sync_out

    def altered(self, slot):
        sync_out(self, slot)
        sess = self.sessions[slot]
        params = list(sess.state)
        params[0] = params[0] * (1 + 1e-2)
        sess.state = params
    setattr_(groups.StackedGroup, "sync_out", altered)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


def plant(name, setattr_=setattr):
    FAULTS[name](setattr_)
    jax.clear_caches()
