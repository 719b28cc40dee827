"""Find copies of a whole stacked decode cache in an optimized HLO module."""
import re

import jax
import jax.numpy as jnp

_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "int32": "s32"}


def whole_stack_copies(hlo: str, cache) -> list:
    """Lines of ``hlo`` that copy (or start copying) a whole stacked cache
    that ``decode_step`` writes: any leaf under ``ssm`` or ``kv``."""
    leaves = jax.tree.leaves({k: cache[k] for k in ("ssm", "kv") if k in cache})
    # compiled HLO gives every shape its layout: bf16[24,128,24,64,128]{4,3,2,1,0}
    shapes = {f"{_HLO_DTYPE[jnp.dtype(a.dtype).name]}[{','.join(map(str, a.shape))}]{{" for a in leaves}
    out = []
    for line in hlo.splitlines():
        m = re.search(r"=\s*(.*?)\s+copy(?:-start)?\(", line)
        if m and any(s in m.group(1) for s in shapes):
            out.append(line.strip())
    return out
