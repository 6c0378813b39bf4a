"""paddle.text — NLP domain utilities.

Reference parity: upstream python/paddle/text/ (unverified, see SURVEY.md
§2.2 "Misc domains"): `ViterbiDecoder`/`viterbi_decode` plus dataset
loaders. Datasets require downloads (this environment has zero egress),
so the loaders accept a local `data_file` and raise a clear error
otherwise.

TPU-native note: Viterbi is a classic sequential DP — realized as a
`lax.scan` over time steps (max-product forward + backtrace), so the
whole decode compiles to one XLA program instead of a Python loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import os
import numpy as np

from ..core.autograd import apply
from ..core.tensor import Tensor, to_tensor
from ..nn.layer import Layer

__all__ = ["ViterbiDecoder", "viterbi_decode", "UCIHousing",
           "LinearChainCrf", "LinearChainCrfLoss"]


def _viterbi_jax(potentials, lengths, trans, include_bos_eos_tag):
    """potentials [B,T,N], lengths [B], trans [N,N] -> (scores, paths)."""
    b, t, n = potentials.shape

    if include_bos_eos_tag:
        # reference semantics: tag N-2 = BOS, N-1 = EOS
        bos_mask = jnp.full((n,), -1e4).at[:n - 2].set(0.0)
        init = potentials[:, 0, :] + trans[n - 2][None, :]
    else:
        init = potentials[:, 0, :]

    def step(carry, xs):
        alpha, idx = carry
        emit, t_idx = xs  # emit [B,N]
        # score[b, i, j] = alpha[b, i] + trans[i, j]
        score = alpha[:, :, None] + trans[None, :, :]
        best_prev = jnp.argmax(score, axis=1)                  # [B,N]
        alpha_new = jnp.max(score, axis=1) + emit              # [B,N]
        # frozen past sequence end
        active = (t_idx < lengths)[:, None]
        alpha_new = jnp.where(active, alpha_new, alpha)
        best_prev = jnp.where(active, best_prev,
                              jnp.arange(n)[None, :])
        return (alpha_new, idx), best_prev

    xs = (jnp.moveaxis(potentials[:, 1:, :], 1, 0),
          jnp.arange(1, t))
    (alpha, _), backptrs = jax.lax.scan(step, (init, 0), xs)
    if include_bos_eos_tag:
        alpha = alpha + trans[:, n - 1][None, :]

    scores = jnp.max(alpha, axis=1)
    last_tag = jnp.argmax(alpha, axis=1)                       # [B]

    def back(carry, bp):
        # carry = tag at time k+1; bp[k] maps it to the tag at time k,
        # which is both the next carry and the emitted path element.
        prev = jnp.take_along_axis(bp, carry[:, None], axis=1)[:, 0]
        return prev, prev

    _, path_rev = jax.lax.scan(back, last_tag, backptrs,
                               reverse=True)
    paths = jnp.concatenate([jnp.moveaxis(path_rev, 0, 1),
                             last_tag[:, None]], axis=1)       # [B,T]
    return scores, paths.astype(jnp.int64)


# one function object each (not a lambda a call), so that apply()'s
# micro-jit holds the two scans compiled instead of compiling them again
def _viterbi_bos_eos(p, tr, ln):
    return _viterbi_jax(p, ln, tr, True)


def _viterbi_plain(p, tr, ln):
    return _viterbi_jax(p, ln, tr, False)


def viterbi_decode(potentials, transition_params, lengths,
                   include_bos_eos_tag=True, name=None):
    potentials = to_tensor(potentials) if not isinstance(potentials, Tensor) \
        else potentials
    transition_params = to_tensor(transition_params) \
        if not isinstance(transition_params, Tensor) else transition_params
    lengths = to_tensor(lengths) if not isinstance(lengths, Tensor) \
        else lengths
    return apply(
        _viterbi_bos_eos if include_bos_eos_tag else _viterbi_plain,
        potentials, transition_params, lengths, name="viterbi_decode")


class ViterbiDecoder(Layer):
    """Reference parity: paddle.text.ViterbiDecoder."""

    def __init__(self, transitions, include_bos_eos_tag=True, name=None):
        super().__init__()
        self.transitions = transitions if isinstance(transitions, Tensor) \
            else to_tensor(transitions)
        self._include = include_bos_eos_tag

    def forward(self, potentials, lengths):
        return viterbi_decode(potentials, self.transitions, lengths,
                              self._include)


class UCIHousing:
    """Reference parity: paddle.text.datasets.UCIHousing, from a local
    whitespace-separated file (no network in this environment)."""

    def __init__(self, data_file=None, mode="train"):
        if data_file is None:
            raise ValueError(
                "this environment has no network access; pass data_file= "
                "pointing at a local housing.data copy")
        raw = np.loadtxt(data_file, dtype=np.float32)
        x, y = raw[:, :-1], raw[:, -1:]
        x = (x - x.mean(0)) / (x.std(0) + 1e-8)
        split = int(0.8 * len(x))
        sl = slice(0, split) if mode == "train" else slice(split, None)
        self.data = list(zip(x[sl], y[sl]))

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        return self.data[i]


class Imdb:
    """Reference parity: paddle.text.datasets.Imdb (upstream
    python/paddle/text/datasets/imdb.py — unverified, SURVEY.md blocker
    notice). Parses a local ``aclImdb_v1.tar.gz``-layout archive
    (aclImdb/{train,test}/{pos,neg}/*.txt) — no network in this
    environment, so `data_file` is required. Builds the word dictionary
    from the TRAIN split with frequency `cutoff` (reference behavior),
    yields (ids int64[], label int64) with label 0=pos, 1=neg
    (reference encoding). Tokenization: lowercase, punctuation stripped,
    whitespace split; the dictionary keeps words with frequency
    STRICTLY greater than `cutoff` (reference semantics).
    """

    def __init__(self, data_file=None, mode="train", cutoff=150):
        import re
        import tarfile
        if data_file is None:
            raise ValueError(
                "this environment has no network access; pass data_file= "
                "pointing at a local aclImdb_v1.tar.gz copy")
        if mode not in ("train", "test"):
            raise ValueError(f"mode must be 'train' or 'test', got {mode!r}")
        self.mode = mode
        pat = re.compile(r"aclImdb/%s/(pos|neg)/.*\.txt$" % mode)
        train_pat = re.compile(r"aclImdb/train/(pos|neg)/.*\.txt$")
        import string
        strip = str.maketrans({c: " " for c in string.punctuation})

        def tokenize(txt):
            return txt.lower().translate(strip).split()

        def _texts(tf, pattern):
            out = []
            for m in tf.getmembers():
                g = pattern.match(m.name)
                if g is None:
                    continue
                txt = tf.extractfile(m).read().decode(
                    "utf-8", errors="ignore")
                out.append((tokenize(txt), 0 if g.group(1) == "pos"
                            else 1))
            return out

        with tarfile.open(data_file) as tf:
            train_docs = _texts(tf, train_pat)
            docs = train_docs if mode == "train" else _texts(tf, pat)

        freq = {}
        for words, _ in train_docs:
            for w in words:
                freq[w] = freq.get(w, 0) + 1
        kept = sorted([w for w, c in freq.items() if c > cutoff],
                      key=lambda w: (-freq[w], w))
        self.word_idx = {w: i for i, w in enumerate(kept)}
        self.word_idx["<unk>"] = unk = len(kept)
        self.docs = [
            (np.array([self.word_idx.get(w, unk) for w in words],
                      np.int64), np.int64(label))
            for words, label in docs]

    def __len__(self):
        return len(self.docs)

    def __getitem__(self, i):
        return self.docs[i]


class Movielens:
    """Reference parity: paddle.text.datasets.Movielens (ml-1m layout:
    ``::``-separated users.dat / movies.dat / ratings.dat inside a local
    zip). Yields (user_id, gender, age, job, movie_id, title_ids,
    category_ids, rating) feature tuples like the reference's
    MovieInfo/UserInfo records, int64-encoded.
    """

    GENDERS = {"M": 0, "F": 1}
    AGES = [1, 18, 25, 35, 45, 50, 56]

    def __init__(self, data_file=None, mode="train", test_ratio=0.1,
                 rand_seed=0):
        import re
        import zipfile
        if data_file is None:
            raise ValueError(
                "this environment has no network access; pass data_file= "
                "pointing at a local ml-1m.zip copy")
        tok = re.compile(r"[A-Za-z0-9]+")
        with zipfile.ZipFile(data_file) as zf:
            def _read(name):
                hits = [n for n in zf.namelist()
                        if n.endswith(name)
                        and not n.startswith("__MACOSX")
                        and not os.path.basename(n).startswith("._")]
                if not hits:
                    raise ValueError(
                        f"{name} not found inside {data_file!r} — "
                        "expected the ml-1m layout")
                return zf.read(hits[0]).decode("latin1").splitlines()

            movies, vocab, cats = {}, {}, {}
            for line in _read("movies.dat"):
                if not line.strip():
                    continue
                mid, title, genres = line.split("::")
                words = tok.findall(title.lower())
                for w in words:
                    vocab.setdefault(w, len(vocab))
                gl = []
                for g in genres.strip().split("|"):
                    cats.setdefault(g, len(cats))
                    gl.append(cats[g])
                movies[int(mid)] = (
                    np.array([vocab[w] for w in words], np.int64),
                    np.array(gl, np.int64))
            users = {}
            for line in _read("users.dat"):
                if not line.strip():
                    continue
                uid, gender, age, job, _zip = line.split("::")
                users[int(uid)] = (self.GENDERS[gender],
                                   self.AGES.index(int(age)), int(job))
            rows = []
            for line in _read("ratings.dat"):
                if not line.strip():
                    continue
                uid, mid, rating, _ts = line.split("::")
                uid, mid = int(uid), int(mid)
                if uid not in users or mid not in movies:
                    continue
                g, a, j = users[uid]
                t_ids, c_ids = movies[mid]
                rows.append((np.int64(uid), np.int64(g), np.int64(a),
                             np.int64(j), np.int64(mid), t_ids, c_ids,
                             np.float32(rating)))
        rng = np.random.default_rng(rand_seed)
        mask = rng.uniform(size=len(rows)) < test_ratio
        self.rows = [r for r, m in zip(rows, mask)
                     if (m if mode == "test" else not m)]
        self.vocab_size = len(vocab)
        self.category_size = len(cats)

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, i):
        return self.rows[i]


__all__ += ["Imdb", "Movielens"]

from .crf import LinearChainCrf, LinearChainCrfLoss  # noqa: E402,F401
