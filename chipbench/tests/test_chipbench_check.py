"""``correct`` holds for the program as it is, and comes out false for
the control and for each fault the serving cells can have, planted in
the timed path underneath an otherwise whole run (toy size, CPU; the
harness's look for a chip is skipped)."""
import _paths  # noqa: F401
import jax.numpy as jnp
import pytest

import tiny
from chipbench import check, run

SECONDS = "1.2"


def _run(paged: bool, seed: int, engine_hook=None):
    args = run.parse(["--workload", "toy", "--seed", str(seed),
                      "--seconds", SECONDS, "--trace", "0"])
    hooks = {"engine": engine_hook} if engine_hook else None
    result, lines = run.run(args, require_tpu=False,
                            resolved=tiny.resolved(paged), hooks=hooks)
    return result, lines


def _wrap_decode(eng, fn):
    decode = eng.decode
    eng.decode = lambda p, b, c: fn(decode, p, b, c)


def state_unchanged(eng):
    """A decode step that hands back the cache it was given."""
    _wrap_decode(eng, lambda d, p, b, c: (c, d(p, b, c)[1]))


def half_batch(eng):
    """A decode step that leaves out half of the batch: the slots that
    hold requests with odd ids get no logits."""
    def fn(d, p, b, c):
        caches, logits = d(p, b, c)
        odd = [s for s, q in enumerate(eng.slot_req)
               if q is not None and q.rid % 2]
        return caches, logits.at[jnp.asarray(odd, jnp.int32)].set(0)
    _wrap_decode(eng, fn)


def token_altered(eng):
    """Every decoded token replaced by its successor id."""
    def fn(d, p, b, c):
        caches, logits = d(p, b, c)
        nxt = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
        return caches, logits.at[jnp.arange(logits.shape[0]), nxt].set(
            logits.max() + 1)
    _wrap_decode(eng, fn)


def page_lost(eng):
    """The memory plane hands back a zeroed page instead of the one
    that was spilled."""
    ensure = eng.pager.ensure
    eng.pager.ensure = lambda pages: {p: jnp.zeros_like(a)
                                      for p, a in ensure(pages).items()}


@pytest.mark.parametrize("paged", [False, True], ids=["resident", "paged"])
def test_program_is_correct(paged):
    result, lines = _run(paged, 2**31 + 21)
    assert result["correct"], lines
    assert result["check"]["max_logit_gap"]["value"] <= \
        result["check"]["max_logit_gap"]["limit"]
    assert list(result)[-1] == "check"
    assert result["attempted"] > 0 and result["failed"] == 0


FAULTS = [(False, state_unchanged), (False, half_batch),
          (False, token_altered), (True, page_lost)]


@pytest.mark.parametrize("paged,fault", FAULTS,
                         ids=[f.__name__ for _, f in FAULTS])
def test_fault_is_not_correct(paged, fault):
    result, lines = _run(paged, 2**31 + 22, engine_hook=fault)
    assert not result["correct"], lines


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_control_is_not_correct(seed):
    """The reference in float8 put in the program's place fails the
    limit that the program passes."""
    r = tiny.resolved(False)
    prep = run.Prepared(r, seed, require_tpu=False)
    ctx, _ = prep.window(seed, float(SECONDS), r["cell"]["rate_rps"])
    nums = check.readings(prep.ref, prep.params, r["config"], r["cell"],
                          r["traffic"], ctx.window, seed, control=True)
    limit = r["cell"]["check"]["max_logit_gap"]
    assert nums["max_logit_gap"] <= limit < nums["control_gap"]


def test_no_tpu_exits_nonzero(capsys):
    rc = run.main(["--workload", "qwen2-0.5b.chat.resident", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_a_tick_that_holds_the_loop_uses_up_no_grace():
    """A profiler writing its trace holds the loop in ``on_tick`` for
    longer than the drain's grace: the requests still live are served
    all the same, since that time is the harness's, not the program's."""
    import time

    from chipbench import driver, traffic_gen, weights
    r = tiny.resolved(False)
    cfg, cell = r["config"], r["cell"]
    eng = driver.build_engine(driver.program_config("toy", cfg),
                              weights.init(cfg, 5), cfg, cell)
    driver.warm_engine(eng, traffic_gen.prompt_buckets(r["traffic"]),
                       cfg["vocab_size"])
    arrivals = traffic_gen.schedule(r["traffic"], 8.0, 1.0,
                                    cfg["vocab_size"], 5)
    origin = time.perf_counter()
    held = []

    def tick(now):
        if not held and now >= origin + 0.5:
            held.append(now)
            time.sleep(3.0)

    recs = driver.Loop(eng, on_tick=tick).run(arrivals, origin,
                                              origin + 1.0, origin + 3.0)
    driver.close_engine(eng)
    assert held and len(recs) > 2
    assert all(x.served for x in recs)
