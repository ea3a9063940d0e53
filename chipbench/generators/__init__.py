"""Traffic generators, one module a generator, found by the name a traffic
file gives under ``generator``.

A generator module has two functions:

- ``generate(params, seed)`` returns the cell's inputs: what its entry's
  ``setup`` and its reference's comparison take.  Same seed, same bytes;
  every seed gives the same sizes.
- ``width(params)`` returns the columns of the table it makes, from the
  parameters alone, for the work models.

What the inputs mean (a binary label, a class index, a real target, raw
records) is agreed between the generator, the entry and the reference; the
harness only hands them on.
"""
