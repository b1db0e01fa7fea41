"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, every
request the window finished (``compared``) is run once through the plain
reference (``reference.gaps``): prompt plus served tokens.  At each served
token the number compared is how far the reference's logit of that token
lies below the reference's best logit.  Greedy serving puts the program's
own best first, so the gap is rounding where the program is sound, and
grows with the error of the arithmetic.
"""
from __future__ import annotations

import numpy as np

from bench import reference

# sequences are padded to a power of two of at least this many tokens (and
# at most ``pad_to``), so a few reference programs serve every length
MIN_PAD = 256


def compared(finished: list) -> list:
    """The requests compared: every one the window finished, in request
    order.  ``finished`` holds (request id, prompt, output tokens)."""
    return sorted(finished, key=lambda r: r[0])


def served_gaps(m: dict, w: dict, picked: list, pad_to: int,
                lowp_targets: bool = False) -> list:
    """Per compared request, the gaps at its served tokens.  With
    ``lowp_targets`` the tokens judged are the control's first choices at
    the same positions instead of the served ones."""
    out = []
    for _rid, prompt, toks in picked:
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        p = len(prompt)
        pad = min(pad_to, max(MIN_PAD, 1 << (len(seq) - 1).bit_length()))
        if lowp_targets:
            top = reference.argmax_lowp(m, w, seq, pad)
            targets = np.concatenate([np.zeros(p - 1, np.int32), top[p - 1 :]])
        else:
            targets = np.concatenate(
                [np.zeros(p - 1, np.int32), np.asarray(toks, np.int32)]
            )
        gap, _best = reference.gaps(m, w, seq, targets, pad)
        out.append(gap[p - 1 :])
    return out
