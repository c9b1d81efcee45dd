"""Dataset + tokenizer pipeline.

Twin of reference `data.py` (get_dataset:7-14, get_tokenizer:18-20,
transform_dataset:23-36), with one structural addition the reference lacks:
an **offline fixture path**. The reference hits the HuggingFace hub at
startup for both the TinyStories dataset and the GPT-2 tokenizer
(data.py:10-19); in a no-egress environment (and in tests — see SURVEY §4)
that is a hard failure. Here, if the hub assets are not in the local cache,
`get_dataset`/`get_tokenizer` fall back to a deterministic synthetic
TinyStories-style corpus and a word-level tokenizer with identical API
surface (`__call__` with padding/truncation, `decode(skip_special_tokens=)`,
settable `pad_token_id` — every recipe sets `pad_token_id = 2` by hand,
reference main-single.py:23).

`transform_dataset` twins the reference semantics — pad to `max_length`,
truncate, drop the text column, dense arrays out (data.py:23-36) — and
accepts either a HuggingFace dataset or the fixture dataset.
"""

from __future__ import annotations

import functools
import os
import re
from typing import Optional, Union

import numpy as np


def _hub_offline() -> None:
    """Fail fast to the fixture instead of retrying the hub for ~30s.
    Locally-cached assets still load in offline mode. Opt back into network
    fetches with TPUKIT_ALLOW_DOWNLOAD=1."""
    if os.environ.get("TPUKIT_ALLOW_DOWNLOAD") != "1":
        os.environ.setdefault("HF_HUB_OFFLINE", "1")
        os.environ.setdefault("HF_DATASETS_OFFLINE", "1")
        os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")

# ---------------------------------------------------------------------------
# Synthetic TinyStories-style corpus (offline fixture).
# ---------------------------------------------------------------------------

_NAMES = ["Tom", "Lily", "Max", "Mia", "Ben", "Sue", "Sam", "Anna", "Tim", "Amy"]
_ANIMALS = ["cat", "dog", "bird", "frog", "bunny", "duck", "bear", "fox", "mouse", "pony"]
_ADJS = ["big", "small", "happy", "sad", "brown", "red", "little", "kind", "funny", "soft"]
_OBJECTS = ["ball", "hat", "book", "cake", "tree", "boat", "kite", "flower", "apple", "box"]
_PLACES = ["park", "garden", "house", "forest", "beach", "farm", "school", "yard", "pond", "hill"]
_VERBS = ["found", "saw", "liked", "wanted", "made", "took", "lost", "shared", "hugged", "chased"]

_TEMPLATES = [
    "One day, {name} went to the {place}. {name} {verb} a {adj} {obj}. "
    'She said "What a {adj} {obj}!" {name} was very {adj2}.',
    "The {adj} {adj2} {animal} lived in the {place}. One day, the {animal} {verb} a {obj}. "
    "The {animal} was {adj2} all day.",
    '{name} had a {adj} {animal}. The {animal} {verb} a {obj} in the {place}. '
    '{name} said "Good {animal}!" and they played together.',
    "One day, {name} and {name2} went to the {place}. They {verb} a {adj} {obj}. "
    '{name2} said "Let us share it." So they did, and they were {adj2}.',
    "There was a {adj} {obj} in the {place}. {name} {verb} it and showed the {animal}. "
    "The {animal} was {adj2}. The end.",
]


def synthetic_stories(num_stories: int, seed: int = 0) -> list[str]:
    """Deterministic TinyStories-like corpus for offline training and tests."""
    rng = np.random.RandomState(seed)
    stories = []
    for _ in range(num_stories):
        t = _TEMPLATES[rng.randint(len(_TEMPLATES))]
        name, name2 = rng.choice(_NAMES, 2, replace=False)
        stories.append(
            t.format(
                name=name,
                name2=name2,
                animal=rng.choice(_ANIMALS),
                adj=rng.choice(_ADJS),
                adj2=rng.choice(_ADJS),
                obj=rng.choice(_OBJECTS),
                place=rng.choice(_PLACES),
                verb=rng.choice(_VERBS),
            )
        )
    return stories


class ListDataset:
    """Minimal text dataset: a list of {"text": str} rows (fixture twin of the
    HF dataset object returned at reference data.py:10-13)."""

    def __init__(self, texts: list[str]):
        self.texts = texts

    def __len__(self):
        return len(self.texts)

    def __getitem__(self, i):
        return {"text": self.texts[i]}


# ---------------------------------------------------------------------------
# Tokenizer.
# ---------------------------------------------------------------------------

# GPT-2-style pieces: a word with optional leading space, punctuation run with
# optional leading space, or whitespace. "".join(pieces) reconstructs the text
# exactly, so decode is lossless.
_PIECE_RE = re.compile(r" ?[A-Za-z0-9']+| ?[^A-Za-z0-9\s]+|\s")

_UNK, _EOS, _PAD = 0, 1, 2  # pad at 2: every recipe sets pad_token_id = 2


class WordTokenizer:
    """Word-level tokenizer with the GPT2Tokenizer API surface the recipes
    use (reference data.py:18-20, utils.py:57,91): callable batching with
    padding/truncation, `decode(..., skip_special_tokens=)`, `vocab_size`,
    `eos_token_id`, settable `pad_token_id`, `model_max_length`.

    Unknown pieces degrade to per-character tokens (all printable ASCII chars
    are in-vocab), so any text round-trips."""

    special_tokens = ["<|unk|>", "<|endoftext|>", "<|pad|>"]

    def __init__(self, corpus: list[str], model_max_length: int = 512):
        pieces = set()
        for text in corpus:
            pieces.update(_PIECE_RE.findall(text))
        # char-level fallback alphabet
        chars = {chr(c) for c in range(32, 127)} | {"\n"}
        vocab_tokens = list(self.special_tokens) + sorted(chars | pieces)
        self._id_to_token = vocab_tokens
        self._token_to_id = {t: i for i, t in enumerate(vocab_tokens)}
        self.model_max_length = model_max_length
        self.pad_token_id = _PAD
        self.eos_token_id = _EOS
        self.unk_token_id = _UNK

    @property
    def vocab_size(self) -> int:
        return len(self._id_to_token)

    def _native_encoder(self):
        """Multithreaded C++ batch encoder (tpukit/native) — the in-tree twin
        of the reference's native fast-tokenizer + num_proc dependency path
        (reference data.py:23-36). None when no compiler is available or
        TPUKIT_NATIVE=0; output is byte-identical to the Python encoder
        (tests/test_native.py)."""
        if not hasattr(self, "_native"):
            try:
                from tpukit import native

                self._native = (
                    native.NativeEncoder(self._id_to_token, self.unk_token_id)
                    if native.is_available()
                    else None
                )
            except Exception:
                self._native = None
        return self._native

    def _encode_one(self, text: str) -> list[int]:
        ids = []
        for piece in _PIECE_RE.findall(text):
            tid = self._token_to_id.get(piece)
            if tid is not None:
                ids.append(tid)
            else:
                ids.extend(self._token_to_id.get(ch, _UNK) for ch in piece)
        return ids

    def __call__(
        self,
        texts,
        padding: Union[bool, str, None] = None,
        max_length: Optional[int] = None,
        truncation: bool = False,
        **_,
    ) -> dict:
        if isinstance(texts, str):
            texts = [texts]
        max_length = max_length or self.model_max_length
        if padding == "max_length" and truncation and len(texts) >= 64:
            native = self._native_encoder()
            if native is not None:
                ids, mask = native.encode_batch(
                    texts, max_length, self.pad_token_id
                )
                return {"input_ids": ids, "attention_mask": mask}
        encoded = [self._encode_one(t) for t in texts]
        if truncation:
            encoded = [ids[:max_length] for ids in encoded]
        if padding == "max_length":
            # Stable output contract regardless of which encoder ran: the
            # padded path always yields [N, max_length] int32 arrays (the
            # native encoder's type), never Python lists.
            input_ids = np.asarray(
                [ids + [self.pad_token_id] * (max_length - len(ids)) for ids in encoded],
                dtype=np.int32,
            )
            attention_mask = np.asarray(
                [[1] * len(ids) + [0] * (max_length - len(ids)) for ids in encoded],
                dtype=np.int32,
            )
        else:
            input_ids = encoded
            attention_mask = [[1] * len(ids) for ids in encoded]
        return {"input_ids": input_ids, "attention_mask": attention_mask}

    def decode(self, ids, skip_special_tokens: bool = False) -> str:
        pieces = []
        specials = {_UNK, _EOS, self.pad_token_id}
        for tid in np.asarray(ids).reshape(-1).tolist():
            if skip_special_tokens and tid in specials:
                continue
            if 0 <= tid < len(self._id_to_token):
                pieces.append(self._id_to_token[tid])
        return "".join(pieces)


_FIXTURE_TRAIN_SIZE = 4096
_FIXTURE_VALIDATION_SIZE = 256


@functools.lru_cache(maxsize=1)
def _fixture_corpus() -> tuple[list[str], list[str]]:
    """Memoized (round-7 host-pipeline hygiene): the corpus is deterministic
    and BOTH get_dataset and get_tokenizer rebuild it on every fit() —
    ~1.3s of pure host regeneration per run that repeat callers (the
    test suite's ~35 fits) were paying each time. Callers treat
    the lists as read-only."""
    return (
        synthetic_stories(_FIXTURE_TRAIN_SIZE, seed=0),
        synthetic_stories(_FIXTURE_VALIDATION_SIZE, seed=1),
    )


# ---------------------------------------------------------------------------
# Public API (reference-parity surface).
# ---------------------------------------------------------------------------


def _warn_fixture_fallback(kind: str, name: str, exc: Exception) -> None:
    """Say loudly which corpus/tokenizer was actually selected: silently
    training on synthetic data when the HF path fails would be a lie in the
    reported metrics."""
    import sys

    print(
        f"tpukit: hub {kind} '{name}' unavailable "
        f"({type(exc).__name__}: {exc}); falling back to the offline "
        f"synthetic fixture {kind}",
        file=sys.stderr,
    )


def _parse_slice(n: int, slice_size: Optional[Union[str, int]]) -> int:
    """Twin of the `train[:{slice_size}]` split-string semantics at reference
    data.py:11: percent strings ("50%"), count strings ("1000"), or ints."""
    if slice_size is None or slice_size == "":
        return n
    if isinstance(slice_size, str):
        if slice_size.endswith("%"):
            return int(n * float(slice_size[:-1]) / 100.0)
        return min(int(slice_size), n)
    return min(int(slice_size), n)


def get_dataset(
    name: str = "roneneldan/TinyStories",
    slice_size: Optional[Union[str, int]] = None,
):
    """Load (train, validation) datasets. Twin of reference data.py:7-14:
    train split is sliceable, validation is always full. Falls back to the
    synthetic fixture corpus when the hub asset is not locally cached."""
    try:
        _hub_offline()
        import datasets  # type: ignore

        train = datasets.load_dataset(
            name,
            split=f"train[:{slice_size}]" if slice_size is not None else "train",
            download_mode="reuse_dataset_if_exists",
        )
        validation = datasets.load_dataset(name, split="validation")
        return train, validation
    except Exception as exc:
        _warn_fixture_fallback("dataset", name, exc)
        train_texts, validation_texts = _fixture_corpus()
        n = _parse_slice(len(train_texts), slice_size)
        return ListDataset(train_texts[:n]), ListDataset(validation_texts)


def get_tokenizer(name: str = "roneneldan/TinyStories-1M", max_length: int = 512):
    """Twin of reference data.py:18-20. HF GPT2Tokenizer when locally cached,
    else the offline WordTokenizer built over the fixture corpus."""
    try:
        _hub_offline()
        from transformers import GPT2Tokenizer  # type: ignore

        return GPT2Tokenizer.from_pretrained(
            name,
            model_max_length=max_length,
            local_files_only=os.environ.get("TPUKIT_ALLOW_DOWNLOAD") != "1",
        )
    except Exception as exc:
        _warn_fixture_fallback("tokenizer", name, exc)
        train_texts, validation_texts = _fixture_corpus()
        return WordTokenizer(train_texts + validation_texts, model_max_length=max_length)


class ArrayDataset:
    """Tokenized dataset as dense numpy arrays — the output format of
    `transform_dataset` (twin of `dataset.set_format("pt")`, reference
    data.py:35, with numpy in place of torch tensors)."""

    def __init__(self, input_ids: np.ndarray, attention_mask: np.ndarray):
        self.input_ids = input_ids
        self.attention_mask = attention_mask

    def __len__(self):
        return self.input_ids.shape[0]

    def __getitem__(self, idx):
        return {
            "input_ids": self.input_ids[idx],
            "attention_mask": self.attention_mask[idx],
        }


def transform_dataset(dataset, tokenizer, max_length: int = 512, num_proc: int = 8) -> ArrayDataset:
    """Tokenize with max-length padding + truncation and drop the text column.
    Twin of reference data.py:23-36. `num_proc` is accepted for CLI parity;
    host-side tokenization here is a single vectorized pass."""
    if hasattr(dataset, "map") and not isinstance(dataset, ListDataset):
        mapped = dataset.map(
            lambda ex: tokenizer(
                ex["text"], padding="max_length", max_length=max_length, truncation=True
            ),
            batched=True,
            remove_columns=["text"],
            num_proc=num_proc,
        )
        mapped.set_format("np")
        return ArrayDataset(
            np.asarray(mapped["input_ids"], dtype=np.int32),
            np.asarray(mapped["attention_mask"], dtype=np.int32),
        )

    texts = [dataset[i]["text"] for i in range(len(dataset))]
    out = tokenizer(texts, padding="max_length", max_length=max_length, truncation=True)
    return ArrayDataset(
        np.asarray(out["input_ids"], dtype=np.int32),
        np.asarray(out["attention_mask"], dtype=np.int32),
    )
