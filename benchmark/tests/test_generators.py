"""The generators: sizes from the traffic file alone, the rest from the seed."""

import json
import os

import numpy as np
import pytest

from lib.cells import BENCH_DIR, load_module


def traffic(name):
    with open(os.path.join(BENCH_DIR, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_crystal_sizes_are_heavy_tailed_and_seed_independent():
    t = traffic("mptrj_fill")
    gen = load_module("generators", t["generator"])
    n = gen.sizes(t["params"])
    assert n.min() >= 2 and n.max() == 444
    assert 20 <= np.median(n) <= 28 and 27 <= n.mean() <= 35
    assert (n > 150).sum() >= 1 and (n <= 10).mean() > 0.05
    small = dict(t["params"], count=40)
    a, b = gen.generate(small, 1), gen.generate(small, 2**31 + 11)
    assert [len(g["z"]) for g in a] == [len(g["z"]) for g in b] == list(gen.sizes(small))
    assert not np.array_equal(a[0]["pos"], b[0]["pos"])
    again = gen.generate(small, 1)
    assert all(np.array_equal(x["pos"], y["pos"]) for x, y in zip(a, again))


def test_crystal_edges_are_the_nearest_images_inside_the_radius():
    t = traffic("mptrj_fill")
    gen = load_module("generators", t["generator"])
    p = dict(t["params"], count=12)
    for g in gen.generate(p, 5):
        n, k = len(g["z"]), p["max_neighbours"]
        assert len(g["senders"]) == n * k  # what makes shapes seed-independent
        vec = g["pos"][g["receivers"]] - g["pos"][g["senders"]] + g["shifts"]
        d = np.linalg.norm(vec, axis=1).reshape(n, k)
        assert d.max() <= p["radius"] and d.min() > 0.5
        if n > 150:  # the brute-force search below is quadratic in images
            continue
        side = float(g["cell"][0, 0])
        offs = np.stack(np.meshgrid(*[np.arange(-4, 5)] * 3, indexing="ij"), -1).reshape(-1, 3)
        cand = (g["pos"][None].astype(float) + offs[:, None] * side).reshape(-1, 3)
        brute = np.linalg.norm(cand[None] - g["pos"][:, None].astype(float), axis=-1)
        brute[brute < 1e-6] = np.inf
        assert np.abs(np.sort(d, 1) - np.sort(brute, 1)[:, :k]).max() < 1e-4


def test_molecule_frames_have_one_shape():
    t = traffic("md17_fill")
    gen = load_module("generators", t["generator"])
    p = dict(t["params"], count=16)
    a, b = gen.generate(p, 3), gen.generate(p, 2**31 + 3)
    z, tmpl, s, r = gen.topology(p)
    assert len(z) == 21 and sorted(np.bincount(z)[[1, 6, 8]]) == [4, 8, 9]
    assert 290 <= len(s) <= 420
    for g in a + b:
        assert len(g["z"]) == 21 and np.array_equal(g["senders"], s)
        assert np.abs(g["pos"] - tmpl).max() <= p["displacement_clip"] + 1e-6
    d = np.linalg.norm(tmpl[:, None] - tmpl[None], axis=-1)[~np.eye(21, dtype=bool)]
    assert d.min() > 0.9
    # every pair the cutoff can reach in any frame is in the list
    inside = d.reshape(21, 20) <= p["radius"] + 2 * np.sqrt(3) * p["displacement_clip"]
    assert inside.sum() <= len(s) or p["skin"] < 2 * np.sqrt(3) * p["displacement_clip"]
    assert not np.array_equal(a[0]["pos"], b[0]["pos"])
