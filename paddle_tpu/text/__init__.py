"""paddle.text parity (reference python/paddle/text/datasets: Imdb, Imikolov,
Movielens, Conll05st, UCIHousing, WMT14/16). No network egress: constructors
take local files; FakeTextDataset gives synthetic sequences for tests."""
from __future__ import annotations

import numpy as np

from ..io import Dataset
from . import datasets  # noqa: F401
from . import decode  # noqa: F401
from . import generation  # noqa: F401
from . import speculative  # noqa: F401
from . import viterbi  # noqa: F401


class FakeTextDataset(Dataset):
    """Synthetic token-sequence dataset (cls-style: ids, label)."""

    def __init__(self, size=1000, seq_len=128, vocab_size=30000,
                 num_classes=2, seed=0):
        self.size = size
        self.seq_len = seq_len
        self.vocab_size = vocab_size
        self.num_classes = num_classes

    def __getitem__(self, idx):
        rng = np.random.RandomState(idx % 65536)
        ids = rng.randint(1, self.vocab_size,
                          size=(self.seq_len,)).astype(np.int64)
        label = np.asarray(idx % self.num_classes, dtype=np.int64)
        return ids, label

    def __len__(self):
        return self.size


from .datasets import (Conll05st, Imdb, Imikolov,  # noqa: F401,E402
                       Movielens, MovieReviews, UCIHousing, WMT14, WMT16)
from . import models  # noqa: F401,E402
from .models import (ErnieConfig, ErnieForPretraining,  # noqa: F401,E402
                     ErnieForSequenceClassification, ErnieModel,
                     NemotronHConfig, NemotronHForCausalLM, ernie_base,
                     ernie_tiny)
