"""The synthetic tokenizer through the program's own HFTokenizer."""

import pytest

from harness import synth_tokenizer as st


@pytest.fixture(scope="module")
def tok(tmp_path_factory):
    from crowdllama_tpu.engine.tokenizer import HFTokenizer, get_tokenizer

    d = tmp_path_factory.mktemp("tok")
    st.write_tokenizer(d, 32000)
    t = get_tokenizer(str(d))
    assert isinstance(t, HFTokenizer)
    return t


def test_every_id_streams_as_its_own_word(tok):
    dec = tok.stream_decoder()
    for i in range(32000):
        assert dec.feed(i) == st.word(i)


def test_no_special_token_ends_or_starts_a_sequence(tok):
    assert (tok.bos_id, tok.eos_id) == (-1, -1)


def test_a_prompt_of_n_words_is_n_ids(tok):
    ids = list(range(0, 32000, 131)) + [31999, 0, 0]
    assert tok.encode(st.text_of(ids)) == ids


def test_streamed_text_without_spaces_reads_back_as_the_same_ids(tok):
    ids = [31999, 0, 17, 17, 4242]
    dec = tok.stream_decoder()
    text = "".join(dec.feed(i) for i in ids)
    assert " " not in text
    assert st.ids_of(text) == ids and tok.encode(text) == ids


def test_words_are_one_to_one():
    words = {st.word(i) for i in range(st.MAX_VOCAB)}
    assert len(words) == st.MAX_VOCAB
    assert all(st.token_id(st.word(i)) == i for i in range(0, st.MAX_VOCAB, 97))


@pytest.mark.parametrize("bad", ["Ab", "abc", "A-c", "ABCD"])
def test_not_a_word(bad):
    with pytest.raises(ValueError):
        st.token_id(bad)


def test_vocabulary_too_large_is_an_error(tmp_path):
    with pytest.raises(ValueError):
        st.write_tokenizer(tmp_path, st.MAX_VOCAB + 1)
