"""The flash kernels of a model that mixes window and full attention layers
(``gluon.nn.MultiHeadAttention(window=...)`` over ``ops/attention.py``),
each kind by its kernels' names in the trace: a windowed call's are
``flash_window_pallas``, ``flash_window_bwd_dkv`` and ``flash_window_bwd_dq``,
a full causal call's ``flash_attention_pallas``, ``flash_attention_bwd_dkv``
and ``flash_attention_bwd_dq``.

``full_flash_fwd_ms_per_step``, ``window_flash_fwd_ms_per_step``
    device time of the kind's forward kernel, a step, all its layers;
``full_flash_bwd_ms_per_step``, ``window_flash_bwd_ms_per_step``
    the same of its two backward kernels (dK/dV and dQ; not the ``jnp``
    around them, which ``flash_bwd.py`` counts under the scope);
``full_flash_fwd_roofline`` ... ``window_flash_bwd_roofline``
    the least time the chip could take for what the kind's layers need,
    over those kernels' time, in %;
``window_blocks_visited_pct``
    of the (query block, key block) pairs of the causal triangle, the share
    in which a windowed call's grid runs a product: the program's own count
    from its shapes (gauges ``graft_flash_blocks_visited{kind="window"}``
    over ``graft_flash_blocks_causal``), set when the call is traced.

What one call needs (``needs``) on B sequences of S, H query heads over G
key/value heads of D under a causal window of W keys (the query's own
included; W = S where there is none): query t sees min(t + 1, W) keys, K =
their sum over a sequence.  Forward: two products a key, 2 * 2 * B*H*D*K
FLOPs, q read and o written for H heads, k and v read for G.  Backward: seven
products a key (the scores and dP in each of the two kernels, dV, dK, dQ),
7 * 2 * B*H*D*K, q, o, dO read and dq written for H heads, k, v read and dk,
dv written for G.  D is the configuration's ``head_dim`` (not ``hidden_size
// num_attention_heads``: ``flash_fwd.py``, ``flash_bwd.py`` and
``gqa_flash.py`` take that, so their shares are not declared for such a
cell).  The layer repeats K/V to H heads before the kernel and the kernels
visit whole blocks, so they move and multiply more than is counted: both
show as a lower share, which is what the metrics are for.  Calls a step: the
entries of ``layer_types`` of the kind.  Against the bf16 peak and the HBM
peak; ``roof`` in the run's facts says which is the larger.

Left out where the configuration has no ``sliding_window``, where the trace
has no kernel of the kind, and, the last metric, where the program has no
such gauge (a commit before it had windows).
"""
import re

from chipbench import peaks

KINDS = {
    "full": ("full_attention", re.compile(r"flash_attention_pallas"),
             re.compile(r"flash_attention_bwd_(dkv|dq)\b")),
    "window": ("sliding_attention", re.compile(r"flash_window_pallas"),
               re.compile(r"flash_window_bwd_(dkv|dq)\b")),
}
_TRACES = "graft_flash_attention_traces_total"
_VISITED, _CAUSAL = "graft_flash_blocks_visited", "graft_flash_blocks_causal"


def keys_seen(seq, window=None):
    """Sum over a sequence's queries of the keys each sees: min(t + 1, W)."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def needs(batch, heads, kv_heads, seq, head_dim, window, dtype_bytes):
    """((FLOPs, bytes) forward, (FLOPs, bytes) backward) of one call."""
    per_product = 2 * batch * heads * head_dim * keys_seen(seq, window)
    q_like = batch * heads * seq * head_dim * dtype_bytes
    kv_like = batch * kv_heads * seq * head_dim * dtype_bytes
    return ((2 * per_product, 2 * q_like + 2 * kv_like),
            (7 * per_product, 4 * q_like + 4 * kv_like))


def _snapshot(run):
    registry = getattr(getattr(run.mx, "telemetry", None), "registry", None)
    return registry().snapshot() if callable(registry) else {}


def _samples(snap, name):
    return snap.get(name, {"samples": []})["samples"]


def traced_kinds(snap):
    """The kinds of causal call the program counted a trace of, by its
    counter's ``window`` label; none where the counter has no such label."""
    found = set()
    for s in _samples(snap, _TRACES):
        window = s["labels"].get("window")
        if window is not None and s["value"]:
            found.add("full" if window == "none" else "window")
    return found


def blocks_visited_pct(snap):
    """{metric: value} from the two gauges, or nothing."""
    by_kind = {name: {s["labels"].get("kind"): s["value"]
                      for s in _samples(snap, name)}
               for name in (_VISITED, _CAUSAL)}
    visited = by_kind[_VISITED].get("window")
    causal = by_kind[_CAUSAL].get("window")
    if not visited or not causal:
        return {}
    return {"window_blocks_visited_pct": 100.0 * visited / causal}


def names(kind):
    return ["%s_flash_%s_%s" % (kind, way, what) for way in ("fwd", "bwd")
            for what in ("ms_per_step", "roofline")]


def read(run):
    sizes = run.sizes
    if "sliding_window" not in sizes or "layer_types" not in sizes:
        return {}
    snap = _snapshot(run)
    out = blocks_visited_pct(snap)
    if out:
        run.facts["mixed_flash_blocks"] = {
            s["labels"].get("kind", "") + ":" + name: s["value"]
            for name in (_VISITED, _CAUSAL) for s in _samples(snap, name)}
    r = run.reduced
    if not r or not r["steps"] or run.peaks is None:
        if run.rehearse and not r:
            # the rehearsal's names: the kinds the program counted a trace of
            for kind in traced_kinds(snap):
                out.update(dict.fromkeys(names(kind), 0.0))
        return out
    traffic, facts = run.traffic, {}
    for kind, (layer_type, fwd, bwd) in KINDS.items():
        calls = list(sizes["layer_types"]).count(layer_type)
        needed = needs(
            traffic["batch_per_chip"], sizes["num_attention_heads"],
            sizes["num_key_value_heads"], traffic["seq_len"],
            sizes["head_dim"],
            sizes["sliding_window"] if kind == "window" else None,
            peaks.DTYPE_BYTES[traffic["dtype"]])
        for way, kernel, (flops, nbytes) in zip(("fwd", "bwd"), (fwd, bwd),
                                                needed):
            kernel_s = sum(s for op, s in r["op_s"].items()
                           if kernel.search(op)) / r["steps"]
            if not kernel_s or not calls:
                continue
            least, roof = peaks.least_time(flops, nbytes, run.peaks)
            stem = "%s_flash_%s" % (kind, way)
            out[stem + "_ms_per_step"] = 1e3 * kernel_s
            out[stem + "_roofline"] = 100.0 * least * calls / kernel_s
            facts[stem] = {"roof": roof, "least_ms_per_call": 1e3 * least,
                           "calls_per_step": calls}
    if facts:
        run.facts["mixed_flash"] = facts
    return out
