"""Closed loop, one client: fresh slices, made from the seed in set-up, are
each planned and segmented in turn through ``Segmenter.segment``, as
reconstructed slices arrive at a beamline.

Parameters: ``max_slices`` made in set-up (the window ends early if it
uses them all), ``check_slices`` drawn from the window's slices for the
check, ``warmup_seed`` of the fixed volume whose slices set-up segments.
Set-up segments its slice 0, so that the fixed-shape programs and the EM
executable are compiled or read from the cache, then its next
``prime_slices`` slices with the cache off (``prime``): a process that
found every program in the cache pays its first real compiles there, not
in the window's first slice.
"""

from __future__ import annotations

import random
from typing import List

import numpy as np

from bench import synthetic
from bench.drivers import Answer, Reservoir, Slice, Window, answer


def setup(sess, cfg, traffic, seed, rec) -> Window:
    n, n_prime = traffic["max_slices"], traffic["prime_slices"]
    shape, corr = cfg["shape"], cfg["corruption"]
    warm, _ = synthetic.make_slices(traffic["warmup_seed"], 1 + n_prime, shape, corr)
    warm = np.asarray(warm)
    images, _ = synthetic.make_slices(seed, n, shape, corr)
    images = np.asarray(images)
    warm_plan = sess.plan(warm[0])
    sess.execute(warm_plan)
    slices = [Slice(i, images[i]) for i in range(n)]
    sample = Reservoir(traffic["check_slices"], random.Random(seed))
    answers = {}
    program_plan = sess.plan
    cursor = [0]

    def timed_plan(image, **kw):
        with rec.span("plan"):
            plan = program_plan(image, **kw)
        enters, evicted = sample.offer(cursor[0])
        if evicted is not None:  # only the sampled slices' plans are checked
            slices[evicted].plan = None
            answers.pop(evicted).segmentation = None
        if enters:
            slices[cursor[0]].plan = plan
        return plan

    sess.plan = timed_plan  # the span sits around the program's own plan

    def step() -> List[Answer]:
        i = cursor[0]
        if i >= n:
            raise StopIteration
        try:
            with rec.span("segment"):
                r = sess.segment(slices[i].image)
        finally:
            cursor[0] += 1
        a = answers[i] = answer(i, r, keep=slices[i].plan is not None)
        return [a]

    def prime():
        for image in warm[1:]:
            sess.execute(program_plan(image))

    return Window(step, slices, {"bucket": list(warm_plan.bucket), "batch": 1}, 1,
                  prime=prime)


def checked_images(cfg, traffic, seed):
    n = traffic["check_slices"]
    images, _ = synthetic.make_slices(seed, n, cfg["shape"], cfg["corruption"])
    return [(i, np.asarray(images[i])) for i in range(n)]
