"""The benchmark's own text: bundled public-domain Shakespeare passages,
and the seeded draws of prompts and training batches made from them.

A copy, kept with the benchmark so that no change to the program's data
module can change what the benchmark feeds it.  ``corpus()`` tiles the
passages to ~400 kB in a fixed shuffled order; ``prompt`` cuts a byte
prompt of a given length at a seeded offset, and ``train_batch`` draws a
next-byte batch for a (seed, step) pair.
"""

from __future__ import annotations

import functools

import numpy as np

PASSAGES = [
    """To be, or not to be, that is the question:
Whether 'tis nobler in the mind to suffer
The slings and arrows of outrageous fortune,
Or to take arms against a sea of troubles
And by opposing end them. To die: to sleep;
No more; and by a sleep to say we end
The heart-ache and the thousand natural shocks
That flesh is heir to, 'tis a consummation
Devoutly to be wish'd. To die, to sleep;
To sleep: perchance to dream: ay, there's the rub;
For in that sleep of death what dreams may come
When we have shuffled off this mortal coil,
Must give us pause.""",
    """Shall I compare thee to a summer's day?
Thou art more lovely and more temperate:
Rough winds do shake the darling buds of May,
And summer's lease hath all too short a date:
Sometime too hot the eye of heaven shines,
And often is his gold complexion dimm'd;
And every fair from fair sometime declines,
By chance or nature's changing course untrimm'd;
But thy eternal summer shall not fade.""",
    """Tomorrow, and tomorrow, and tomorrow,
Creeps in this petty pace from day to day
To the last syllable of recorded time,
And all our yesterdays have lighted fools
The way to dusty death. Out, out, brief candle!
Life's but a walking shadow, a poor player
That struts and frets his hour upon the stage
And then is heard no more: it is a tale
Told by an idiot, full of sound and fury,
Signifying nothing.""",
    """But, soft! what light through yonder window breaks?
It is the east, and Juliet is the sun.
Arise, fair sun, and kill the envious moon,
Who is already sick and pale with grief,
That thou her maid art far more fair than she.""",
    """Friends, Romans, countrymen, lend me your ears;
I come to bury Caesar, not to praise him.
The evil that men do lives after them;
The good is oft interred with their bones;
So let it be with Caesar. The noble Brutus
Hath told you Caesar was ambitious:
If it were so, it was a grievous fault,
And grievously hath Caesar answer'd it.""",
    """All the world's a stage,
And all the men and women merely players:
They have their exits and their entrances;
And one man in his time plays many parts,
His acts being seven ages. At first the infant,
Mewling and puking in the nurse's arms.""",
    """Now is the winter of our discontent
Made glorious summer by this sun of York;
And all the clouds that lour'd upon our house
In the deep bosom of the ocean buried.
Now are our brows bound with victorious wreaths;
Our bruised arms hung up for monuments.""",
    """The quality of mercy is not strain'd,
It droppeth as the gentle rain from heaven
Upon the place beneath: it is twice blest;
It blesseth him that gives and him that takes:
'Tis mightiest in the mightiest: it becomes
The throned monarch better than his crown.""",
    """If music be the food of love, play on;
Give me excess of it, that, surfeiting,
The appetite may sicken, and so die.
That strain again! it had a dying fall:
O, it came o'er my ear like the sweet sound,
That breathes upon a bank of violets,
Stealing and giving odour!""",
    """Once more unto the breach, dear friends, once more;
Or close the wall up with our English dead.
In peace there's nothing so becomes a man
As modest stillness and humility:
But when the blast of war blows in our ears,
Then imitate the action of the tiger;
Stiffen the sinews, summon up the blood.""",
]


@functools.lru_cache(maxsize=1)
def corpus(target_bytes: int = 400_000) -> np.ndarray:
    """The passages tiled in a fixed shuffled order, as uint8 bytes."""
    rng = np.random.default_rng(0)
    chunks, size = [], 0
    while size < target_bytes:
        for i in rng.permutation(len(PASSAGES)):
            chunks.append(PASSAGES[i].encode() + b"\n\n")
            size += len(chunks[-1])
    data = np.frombuffer(b"".join(chunks), np.uint8).copy()
    data.flags.writeable = False
    return data


def prompt(rng: np.random.Generator, length: int) -> list:
    """A ``length``-byte cut of the corpus at an offset drawn from ``rng``."""
    data = corpus()
    start = int(rng.integers(0, len(data) - length))
    return data[start:start + length].tolist()


def train_batch(seed: int, step: int, batch: int, seq_len: int) -> dict:
    """Next-byte batch for (seed, step): rows start at seeded offsets."""
    data = corpus()
    rng = np.random.default_rng([seed, step])
    starts = rng.integers(0, len(data) - seq_len - 1, size=batch)
    tokens = np.stack([data[s:s + seq_len] for s in starts]).astype(np.int32)
    labels = np.stack([data[s + 1:s + seq_len + 1]
                       for s in starts]).astype(np.int32)
    return {"tokens": tokens, "labels": labels}
