"""The token records: the codec round-trips, the draw is a pure function
of (seed, index), and the program's native parser reads what the
benchmark's own writer wrote."""

import numpy as np

from benchmark.lib import tfrecord, token_traffic


def test_varints_round_trip_up_to_63_bits():
  values = np.array([0, 1, 127, 128, 16383, 16384, 25023, 2 ** 31 + 5,
                     2 ** 49 - 1, 2 ** 49, 2 ** 62 + 3], np.int64)
  packed = token_traffic.encode_varints(values)
  assert packed[:4] == bytes([0, 1, 127, 128])       # 128 -> 0x80 0x01
  np.testing.assert_array_equal(token_traffic.decode_varints(packed), values)
  assert token_traffic.decode_varints(b'').shape == (0,)


def test_example_round_trips_and_frames(tmp_path):
  ids = token_traffic.draw(3000000007, 5, 8192, 25024, 1.0)
  assert ids.dtype == np.int64 and ids.shape == (8192,)
  assert 0 <= ids.min() and ids.max() < 25024
  payload = token_traffic.encode_example('tokens', ids)
  np.testing.assert_array_equal(
      token_traffic.decode_example(payload)['tokens'], ids)
  path = tmp_path / 'one.tfrecord'
  path.write_bytes(tfrecord.frame(payload))
  (back,) = list(tfrecord.read_records(str(path)))
  assert back == payload


def test_draw_is_a_pure_function_of_seed_and_index():
  a = token_traffic.draw(7, 3, 256, 1000, 1.0)
  np.testing.assert_array_equal(a, token_traffic.draw(7, 3, 256, 1000, 1.0))
  assert not np.array_equal(a, token_traffic.draw(7, 4, 256, 1000, 1.0))
  assert not np.array_equal(a, token_traffic.draw(8, 3, 256, 1000, 1.0))
  # Zipf with exponent 1: rank 1 is drawn about twice as often as rank 2.
  many = token_traffic.draw(1, 0, 200000, 1000, 1.0)
  share = np.bincount(many, minlength=1000) / many.size
  assert 1.8 < share[0] / share[1] < 2.2
  assert abs(share[0] - 1 / np.sum(1 / np.arange(1, 1001))) < 0.01


def test_shards_hold_every_example_once(tmp_path):
  mix = {'num_examples': 10, 'num_shards': 3, 'sequence_length': 64,
         'tokens': {'feature': 'tokens', 'zipf_exponent': 1.0}}
  pattern, index_of, size = token_traffic.write_shards(
      str(tmp_path / 's'), 11, mix, 96)
  examples = token_traffic.read_examples(pattern, 'tokens')
  assert len(examples) == 10 and len(index_of) == 10 and size > 64
  for index, ids in enumerate(examples):
    assert index_of[token_traffic.digest(ids)] == index
    np.testing.assert_array_equal(ids,
                                  token_traffic.draw(11, index, 64, 96, 1.0))
