import asyncio, time, os, json
os.environ.setdefault("BENCH_CONCURRENCY", "128")
os.environ.setdefault("BENCH_REQUESTS", "256")
import jax
from dynamo_tpu.utils.jax_env import configure_compile_cache

configure_compile_cache()
import bench as B
from dynamo_tpu.engines.tpu import engine as eng_mod

events = []
orig_rd = eng_mod.JaxEngine._run_decode
orig_rs = eng_mod.JaxEngine._run_step
def rd(self, *a, **k):
    t0 = time.perf_counter(); r = orig_rd(self, *a, **k)
    events.append(("decode", t0, time.perf_counter()-t0)); return r
def rs(self, *a, **k):
    t0 = time.perf_counter(); r = orig_rs(self, *a, **k)
    events.append(("prefill", t0, time.perf_counter()-t0)); return r
eng_mod.JaxEngine._run_decode = rd
eng_mod.JaxEngine._run_step = rs
asyncio.run(B.run_bench())
# steady state = events in the last 60% of the timeline
t_lo = events[0][1] + 0.4*(events[-1][1]-events[0][1])
for kind in ("decode", "prefill"):
    sel = [d for k,t,d in events if k==kind and t>=t_lo]
    if sel:
        print(f"{kind}: n={len(sel)} avg={sum(sel)/len(sel)*1000:.1f}ms max={max(sel)*1000:.1f}ms")
# device-busy fraction over steady window
busy = sum(d for k,t,d in events if t>=t_lo)
span = events[-1][1]+events[-1][2]-t_lo
print(f"device-dispatch busy: {busy:.2f}s of {span:.2f}s ({busy/span*100:.0f}%)")
