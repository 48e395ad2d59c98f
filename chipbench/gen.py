"""Traffic generators: everything a run feeds the program is made here
from ``--seed`` and the parameters of a traffic file.

These are the benchmark's own copies of the program's generators
(``repro.data.synthetic.make_classification_dataset``,
``repro.core.partition.partition_dirichlet``,
``repro.runtime.workload.poisson_arrivals``), so that no change to the
program can change what the benchmark feeds it. The images are made on
the device in one jitted call; the partition and the request trace are
made on the host.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Tuple

import numpy as np


# --------------------------------------------------------------------------
# images: CIFAR-shaped synthetic classes (templates, shifts, noise)
# --------------------------------------------------------------------------

def images_fn(num: int, num_classes: int, image_size: int,
              freq: int = 4, template_scale: float = 1.5,
              max_shift: int = 3, noise: float = 1.4):
    """A jittable ``key -> (images (N, H*W*3) f32 in [-1,1], labels (N,))``.

    Each class is a smooth random template (a ``freq x freq`` field of
    colours blown up to the image size, drawn from a fixed template key so
    that every seed sees the same concepts); each sample is its class's
    template rolled by up to ``max_shift`` pixels each way, plus Gaussian
    noise, through ``tanh``. The images come flat, pixel-major with the 3
    channels last, so that no axis of 3 is padded to the chip's tiles;
    ``reshape(N, H, W, 3)`` gives NHWC.
    """
    import jax
    import jax.numpy as jnp
    rep = image_size // freq
    hw3 = image_size * image_size * 3

    def make(key):
        kl, ks, kn = jax.random.split(key, 3)
        base = jax.random.normal(jax.random.PRNGKey(1234),
                                 (num_classes, freq, freq, 3)) * template_scale
        templates = jnp.repeat(jnp.repeat(base, rep, axis=1), rep, axis=2)
        templates = templates.reshape(num_classes * hw3)
        labels = jax.random.randint(kl, (num,), 0, num_classes)
        shifts = jax.random.randint(ks, (num, 2), -max_shift, max_shift + 1)
        flat = jnp.arange(hw3)
        i = flat // (image_size * 3)
        j = (flat // 3) % image_size
        c = flat % 3
        src_i = (i[None, :] - shifts[:, :1]) % image_size
        src_j = (j[None, :] - shifts[:, 1:]) % image_size
        idx = labels[:, None] * hw3 + (src_i * image_size + src_j) * 3 \
            + c[None, :]
        imgs = templates[idx] + noise * jax.random.normal(kn, (num, hw3))
        return jnp.tanh(imgs).astype(jnp.float32), labels.astype(jnp.int32)

    return make


def dirichlet_partition(labels: np.ndarray, num_clients: int,
                        num_classes: int, classes_per_client: int,
                        concentration: float, seed: int
                        ) -> List[np.ndarray]:
    """Extended-Dirichlet split: each client holds ``classes_per_client``
    classes; within a class the holders' shares are
    Dirichlet(``concentration``), so dataset sizes vary strongly. Every
    client ends with at least one sample. Returns per-client sorted
    sample indices."""
    rng = np.random.default_rng(seed)
    holders: List[List[int]] = [[] for _ in range(num_classes)]
    slots: List[int] = []
    for _ in range(classes_per_client):
        slots.extend(rng.permutation(num_clients).tolist())
    for i, client in enumerate(slots):
        holders[i % num_classes].append(client)
    for m in range(num_classes):
        if not holders[m]:
            holders[m].append(int(rng.integers(num_clients)))
    owned: List[List[int]] = [[] for _ in range(num_clients)]
    for m in range(num_classes):
        idx = np.flatnonzero(labels == m)
        rng.shuffle(idx)
        shares = rng.dirichlet(np.full(len(holders[m]), concentration))
        counts = np.floor(shares * idx.size).astype(np.int64)
        counts[-1] = idx.size - counts[:-1].sum()
        start = 0
        for holder, c in zip(holders[m], counts):
            owned[holder].extend(idx[start:start + c].tolist())
            start += c
    sizes = np.array([len(o) for o in owned])
    for k in np.flatnonzero(sizes == 0):
        donor = int(np.argmax([len(o) for o in owned]))
        owned[k].append(owned[donor].pop())
    return [np.sort(np.asarray(o, np.int64)) for o in owned]


def class_counts(labels: np.ndarray, parts: List[np.ndarray],
                 num_classes: int) -> np.ndarray:
    out = np.zeros((len(parts), num_classes), np.int64)
    for k, p in enumerate(parts):
        if p.size:
            out[k] = np.bincount(labels[p], minlength=num_classes)
    return out


# --------------------------------------------------------------------------
# request traces: a fixed schedule, token ids from the seed
# --------------------------------------------------------------------------

def lognormal_quantiles(n: int, median: float, sigma: float) -> np.ndarray:
    """The n mid-quantiles of a lognormal: a sample of the law with no
    sampling noise, so that every seed gets the same set of sizes."""
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    return median * np.exp(sigma * z)


def exponential_gaps(n: int, rate_per_s: float) -> np.ndarray:
    """The n mid-quantiles of Exp(rate): Poisson inter-arrival gaps with
    no sampling noise."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate_per_s


def round_up_to_menu(x: np.ndarray, menu: List[int]) -> np.ndarray:
    menu_arr = np.asarray(sorted(menu))
    idx = np.searchsorted(menu_arr, np.ceil(x), side="left")
    return menu_arr[np.minimum(idx, len(menu_arr) - 1)]


def request_trace(traffic: Dict, seed: int, window_s: float,
                  vocab_size: int) -> List[Dict]:
    """Open-loop requests due in ``[0, window_s)``.

    The schedule (arrival times, prompt and output lengths) is one
    Poisson trace drawn from the traffic file's ``schedule_seed``, the
    same in every run: a seed that only reorders the same sizes and gaps
    still moves the tail of the time to first token several-fold, which
    is the order's doing and not the program's. ``seed`` draws the token
    ids. The sizes are the mid-quantiles of their laws: prompt lengths
    lognormal rounded up to the traffic's menu, output lengths lognormal
    and clipped; the gaps are the mid-quantiles of the exponential law.
    """
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * window_s)))
    order = np.random.default_rng(traffic["schedule_seed"])
    p = traffic["prompt"]
    o = traffic["output"]
    plens = round_up_to_menu(
        lognormal_quantiles(n, p["median"], p["sigma"]), p["menu"])
    olens = np.clip(np.round(lognormal_quantiles(n, o["median"],
                                                 o["sigma"])),
                    o["min"], o["max"]).astype(np.int64)
    plens = plens[order.permutation(n)]
    olens = olens[order.permutation(n)]
    gaps = exponential_gaps(n, rate)[order.permutation(n)]
    arrivals = np.cumsum(gaps) - gaps[0]
    if n > 1:
        arrivals *= window_s * (n - 0.5) / n / arrivals[-1]
    tokens = np.random.default_rng([int(seed), 0x70C5])
    return [{"rid": i, "arrival_s": float(arrivals[i]),
             "prompt": tokens.integers(0, vocab_size, int(plens[i]),
                                       dtype=np.int64).astype(np.int32),
             "max_new_tokens": int(olens[i])} for i in range(n)]


def sample_rows(n: int, k: int, seed: int, must: Tuple[int, ...] = ()
                ) -> List[int]:
    """``k`` distinct indices of ``range(n)`` drawn from the seed, with
    the ``must`` indices among them."""
    rng = np.random.default_rng([int(seed), 0xC0DE])
    rest = [i for i in rng.permutation(n).tolist() if i not in must]
    return list(must) + rest[:max(0, k - len(must))]
