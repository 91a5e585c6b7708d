"""The comparison that decides ``correct``.

A sample of the requests the timed engine finished (drawn from the seed,
the longest among them) is run once through the plain reference, over its
prompt and its served tokens.  Two numbers are compared with their limits:

- ``kv0_err``: the K and V that the timed path wrote into the page pool at
  layer 0 for every sampled position (prefill chunks and decode steps
  write them, each through the fused W4A4+LRC kernel, at chunk and at
  decode M), against the reference's, as ||got - ref|| / ||ref|| over the
  sample.  Where an activation's x / s lies on a rounding tie (within
  ``TIE`` of a half step), either rounding is the configuration's
  quantizer, and implementations resolve such ties differently; so at a
  position with ties the reference's candidate nearest to what the
  program wrote is taken, one per choice of rounding at the tied
  elements.  Deeper layers are not compared: one tie resolved the other
  way changes a whole row of a linear's output, and such flips compound
  layer by layer until any two correct computations disagree (PERF.md,
  "How correct is decided").
- ``gap_mean``: the mean gap by which a served (greedy) token's reference
  logit lies below the reference's best logit at that position.  The
  logits themselves are chaotic under those flips, but a token altered
  where it is produced, or one served from a broken attention, lands far
  below the best on average.

A control is the reference itself one precision step lower (``CONTROLS``),
read the same way, with its own K/V and first-ranked tokens in the
program's place.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench.model import family

# the numbers a cell's file may hold to a limit: each is an upper limit
LIMITED = ("kv0_err", "gap_mean", "gap_max")
TIE = 1e-5     # |x / s - (k + 1/2)| under which either rounding stands
MAX_TIES = 10  # ties at one position beyond which only single flips count


def reference(spec):
    return family(spec.reference)


def _round(x, dtype="bfloat16"):
    return np.asarray(x, np.float32).astype(getattr(ml_dtypes, dtype)) \
        .astype(np.float32)


class Layer0:
    """Layer 0's K and V of a sequence and their candidates under ties."""

    def __init__(self, spec, w):
        ref = reference(spec)
        self.spec = spec
        self.rows = {}
        for name in ref.KV_LINEARS:
            p = ref.layer_weights(w, 0)["lin"][name]
            self.rows[name] = (np.asarray(ref.unpack(p["qweight"])),
                               np.asarray(p["w_scale"], np.float32))

    def parts(self, w, tokens, n, precision="highest"):
        """The reference's layer-0 parts of ``tokens`` (first ``n``)."""
        ref = reference(self.spec)
        x = w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        out = ref.layer0_parts(self.spec, ref.layer_weights(w, 0), x,
                               precision)
        return {k: np.asarray(v)[:n] for k, v in out.items()}

    def stored(self, parts):
        """K and V as the cache stores them."""
        n = parts["yk"].shape[0]
        return reference(self.spec).store_kv(self.spec, parts["yk"],
                                             parts["yv"], np.arange(n))

    def error(self, parts, k_got, v_got):
        """(squared error, squared norm of the reference, positions with a
        tie) over the positions, each with the tie choice nearest to
        ``k_got``/``v_got``."""
        t, q, s = parts["t"], parts["q"], parts["s"]
        yk, yv = parts["yk"], parts["yv"]
        k_ref, v_ref = self.stored(parts)
        ref2 = float(np.sum(k_ref ** 2, dtype=np.float64)
                     + np.sum(v_ref ** 2, dtype=np.float64))
        err = (np.sum((k_got - k_ref) ** 2, -1, dtype=np.float64)
               + np.sum((v_got - v_ref) ** 2, -1, dtype=np.float64))
        qmax = 2 ** (self.spec.act_bits - 1) - 1
        lo = np.floor(t)
        alt = np.clip(np.where(q == lo, lo + 1, lo), -qmax - 1, qmax)
        tied = (np.abs(t - lo - 0.5) < TIE) & (alt != q)
        (wk, sk), (wv, sv) = self.rows.values()
        for p in np.nonzero(tied.any(-1) & (err > 0))[0]:
            js = np.nonzero(tied[p])[0]
            step = (alt[p, js] - q[p, js]) * s[p, 0]
            dk = step[:, None] * wk[js] * sk
            dv = step[:, None] * wv[js] * sv
            if js.size <= MAX_TIES:
                choices = np.asarray(list(itertools.product(
                    (0, 1), repeat=js.size))[1:], np.float32)
            else:
                choices = np.eye(js.size, dtype=np.float32)
            ck, cv = reference(self.spec).store_kv(
                self.spec, yk[p] + choices @ dk, yv[p] + choices @ dv,
                np.full(len(choices), p))
            e = (np.sum((k_got[p] - ck) ** 2, -1, dtype=np.float64)
                 + np.sum((v_got[p] - cv) ** 2, -1, dtype=np.float64))
            err[p] = min(err[p], float(e.min()))
        return float(err.sum()), ref2, int(tied.any(-1).sum())


def run_reference(spec, w, prompt, out, seq_len: int,
                  precision: str = "highest", kv_dtype: str = None):
    """Reference logits at the positions that produced each served token
    (n_out, V), on the device, over prompt + served tokens padded to
    ``seq_len`` (one compiled shape; the causal mask keeps the padding out
    of every real position).  Also the padded tokens and their length."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(out[:-1])])
    n = seq.size
    toks = np.zeros(seq_len, np.int32)
    toks[:n] = seq
    logits, _ = reference(spec).forward(spec, w, jnp.asarray(toks),
                                        precision, 0, kv_dtype)
    p0 = len(prompt) - 1
    return logits[p0:p0 + len(out)], toks, n


@jax.jit
def _gaps(logits, tokens):
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best - got


class Comparison:
    """Accumulates the numbers over the sampled requests."""

    def __init__(self):
        self.err2 = 0.0
        self.ref2 = 0.0
        self.gaps = []
        self.positions = 0
        self.tied_positions = 0

    def add_tokens(self, ref_logits, served):
        served = jnp.asarray(np.asarray(served, np.int32))
        self.gaps.extend(np.asarray(_gaps(ref_logits, served)).tolist())

    def add_kv(self, err2, ref2, tied, n):
        self.err2 += err2
        self.ref2 += ref2
        self.tied_positions += tied
        self.positions += n

    @property
    def tokens(self):
        return len(self.gaps)

    def numbers(self) -> dict:
        gaps = np.asarray(self.gaps)
        return {
            "kv0_err": ((self.err2 / self.ref2) ** 0.5 if self.ref2 > 0
                        else float("inf")),
            "gap_mean": float(gaps.mean()) if gaps.size else float("inf"),
            "gap_max": float(gaps.max()) if gaps.size else float("inf"),
            "agree_share": (float(np.mean(gaps == 0)) if gaps.size
                            else 0.0),
            "tied_share": self.tied_positions / max(1, self.positions),
        }


def compare_served(spec, w, samples, seq_len: int) -> Comparison:
    """``samples``: (prompt, served tokens, pool K, pool V) of each sampled
    request, with layer 0's K/V first, as ``Stepper.kept_kv`` holds them."""
    cmp = Comparison()
    l0 = Layer0(spec, w)
    for prompt, out, k_got, v_got in samples:
        logits, toks, n = run_reference(spec, w, prompt, out, seq_len)
        cmp.add_tokens(logits, out)
        cmp.add_kv(*l0.error(l0.parts(w, toks, n), k_got[0], v_got[0]), n)
    return cmp


# The controls: the reference in the program's place, one step below the
# precision the configuration states.  "fp8_lrc" stores U and V of the
# low-rank term in float8_e4m3fn instead of bfloat16 (the control the
# limits are set against); "fp8_kv" stores K and V in float8_e4m3fn
# instead of bfloat16; "high" runs the float32 matmuls (the low-rank term,
# attention, unembedding) as three bf16 passes instead of "highest".
CONTROLS = {
    "fp8_lrc": {"lrc_dtype": "float8_e4m3fn"},
    "fp8_kv": {"kv_dtype": "float8_e4m3fn"},
    "high": {"precision": "high"},
}


def lowered(w, dtype):
    """``w`` with every U and V rounded through ``dtype``."""
    lin = {name: {**p, "u": p["u"].astype(dtype).astype(p["u"].dtype),
                  "v": p["v"].astype(dtype).astype(p["v"].dtype)}
           for name, p in w["lin"].items()}
    return {**w, "lin": lin}


def compare_control(spec, w, samples, seq_len: int,
                    control: str) -> Comparison:
    """A control on the same prompts and served tokens, read like the
    program: its own layer-0 K/V, and at each position the token it ranks
    first."""
    c = CONTROLS[control]
    wl = lowered(w, c["lrc_dtype"]) if "lrc_dtype" in c else w
    precision = c.get("precision", "highest")
    cmp = Comparison()
    l0 = Layer0(spec, w)
    for prompt, out, _, _ in samples:
        ref_logits, toks, n = run_reference(spec, w, prompt, out, seq_len)
        low_logits, _, _ = run_reference(spec, wl, prompt, out, seq_len,
                                         precision, c.get("kv_dtype"))
        cmp.add_tokens(ref_logits, np.asarray(jnp.argmax(low_logits, -1)))
        k_low, v_low = l0.stored(l0.parts(wl, toks, n, precision))
        if "kv_dtype" in c:
            k_low = _round(k_low, c["kv_dtype"])
            v_low = _round(v_low, c["kv_dtype"])
        cmp.add_kv(*l0.error(l0.parts(w, toks, n), k_low, v_low), n)
    return cmp


def verdict(numbers: dict, limits: dict):
    """(correct, lines): each number beside its limit."""
    unknown = set(limits) - set(LIMITED)
    if unknown:
        raise ValueError(f"no number {sorted(unknown)} to compare; "
                         f"a limit names one of {LIMITED}")
    lines = []
    ok = True
    for name in sorted(limits):
        value = numbers[name]
        good = value <= limits[name]
        ok &= good
        lines.append(f"{name} {value:.6g} limit {limits[name]:.6g} "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines
