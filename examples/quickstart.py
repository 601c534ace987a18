"""Quickstart: the three layers of the framework in one script.

1. FLEXIFLOW carbon model — pick the carbon-optimal FlexiBits core for a
   food-spoilage patch at two different deployment lifetimes (the paper's
   headline result: lifetime changes the answer).
2. FlexiBench on the ISS — run the food-spoilage workload bit-exactly on
   the JAX RV32E simulator and compare with the functional reference.
3. LM stack — train a few steps of a reduced qwen2-1.5b and decode.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import compile_cache

compile_cache.enable()

# ---------------------------------------------------------------- 1. carbon
from repro.core.selection import optimal_core
from repro.core.carbon import DeviceProfile
from repro.flexibench.base import get, WEEK_S, MONTH_S
from repro.flexibits.pyiss import PyISS

fs = get("FS")
rng = np.random.default_rng(0)
x = fs.gen_inputs(rng, 1)[0]
sim = PyISS(fs.program.code, fs.total_mem_words,
            fs.initial_memory(x)).run()
prof = DeviceProfile(sim.n_instr - sim.n_two_stage, sim.n_two_stage,
                     vm_kb=0.1, nvm_kb=fs.nvm_kb)
for name, lifetime in [("meat (1 week)", WEEK_S),
                       ("rice (6 months)", 6 * MONTH_S)]:
    core, totals = optimal_core(prof, lifetime_s=lifetime,
                                execs_per_day=24)
    print(f"[carbon] {name:16s} -> {core.name}  "
          + " ".join(f"{k}={v * 1e3:.2f}g" for k, v in totals.items()))

# ---------------------------------------------------------------- 2. ISS
import jax.numpy as jnp
from repro.flexibits import iss

state = iss.run(jnp.asarray(fs.program.code.view(np.int32)),
                jnp.asarray(fs.initial_memory(x)), fs.max_steps)
print(f"[iss] spoilage class={int(state.mem[fs.out_addr])} "
      f"(ref={int(fs.ref(x[None])[0])}) in {int(state.n_instr)} instrs, "
      f"mix={dict(zip(iss.MIX_CLASSES, map(int, state.mix)))}")

# ---------------------------------------------------------------- 3. LM
from repro.configs.registry import get_smoke_config
from repro.launch.train import train_loop
from repro.launch.serve import generate

cfg = get_smoke_config("qwen2-1.5b")
out = train_loop(cfg=cfg, steps=5, batch=4, seq=64, ckpt_dir="",
                 log=lambda *a: None)
print(f"[lm] 5 train steps: loss {out['losses'][0]:.3f} -> "
      f"{out['losses'][-1]:.3f}")
toks, stats = generate(cfg, batch=2, prompt_len=8, gen=8,
                       params=out["params"], log=lambda *a: None)
print(f"[lm] generated {toks.shape} tokens "
      f"({stats['decode_s'] * 1e3:.0f}ms decode)")
print("quickstart OK")
