"""Chat models (parity: xpacks/llm/llms.py:97-547).

OpenAI/LiteLLM/Cohere chats are API-gated; ``HFPipelineChat`` runs a local
transformers pipeline when a model is cached.  ``prompt_chat_single_qa``
mirrors the reference helper.  All chats are async UDFs so concurrent rows
of an epoch fan out together.
"""

from __future__ import annotations

from typing import Any

from pathway_tpu.engine.types import Json
from pathway_tpu.internals.expression import ColumnExpression
from pathway_tpu.internals.udfs import UDF, async_executor
import pathway_tpu.internals.expression as expr_mod


class BaseChat(UDF):
    """Common surface: __call__(messages) where messages is a chat list."""

    def _accepts_call_arg(self, arg_name: str) -> bool:
        return True


def _messages_to_prompt(messages: Any) -> str:
    if isinstance(messages, Json):
        messages = messages.value
    if isinstance(messages, str):
        return messages
    if isinstance(messages, (list, tuple)):
        parts = []
        for m in messages:
            if isinstance(m, Json):
                m = m.value
            if isinstance(m, dict):
                parts.append(f"{m.get('role', 'user')}: {m.get('content', '')}")
            else:
                parts.append(str(m))
        return "\n".join(parts)
    return str(messages)


class OpenAIChat(BaseChat):
    """OpenAI chat (parity: llms.py:97). Gated on `openai`."""

    def __init__(
        self,
        capacity: int | None = None,
        model: str | None = "gpt-3.5-turbo",
        retry_strategy=None,
        cache_strategy=None,
        **openai_kwargs,
    ):
        super().__init__(
            executor=async_executor(capacity=capacity, retry_strategy=retry_strategy),
            cache_strategy=cache_strategy,
        )
        self.model = model
        self.kwargs = dict(openai_kwargs)

        async def chat(messages: Any, **kwargs) -> str | None:
            import openai  # gated

            client = openai.AsyncOpenAI()
            if isinstance(messages, Json):
                messages = messages.value
            if isinstance(messages, str):
                messages = [{"role": "user", "content": messages}]
            params = {"model": self.model, **self.kwargs, **kwargs}
            ret = await client.chat.completions.create(messages=messages, **params)
            return ret.choices[0].message.content

        self.__wrapped__ = chat


class LiteLLMChat(BaseChat):
    """LiteLLM chat (parity: llms.py). Gated on `litellm`."""

    def __init__(
        self,
        capacity: int | None = None,
        model: str | None = None,
        retry_strategy=None,
        cache_strategy=None,
        **litellm_kwargs,
    ):
        super().__init__(
            executor=async_executor(capacity=capacity, retry_strategy=retry_strategy),
            cache_strategy=cache_strategy,
        )
        self.model = model
        self.kwargs = dict(litellm_kwargs)

        async def chat(messages: Any, **kwargs) -> str | None:
            import litellm  # gated

            if isinstance(messages, Json):
                messages = messages.value
            if isinstance(messages, str):
                messages = [{"role": "user", "content": messages}]
            ret = await litellm.acompletion(
                model=self.model, messages=messages, **{**self.kwargs, **kwargs}
            )
            return ret.choices[0]["message"]["content"]

        self.__wrapped__ = chat


class CohereChat(BaseChat):
    """Cohere chat with citations (parity: llms.py:~547). Gated on `cohere`."""

    def __init__(
        self,
        capacity: int | None = None,
        model: str | None = "command",
        retry_strategy=None,
        cache_strategy=None,
        **cohere_kwargs,
    ):
        super().__init__(
            executor=async_executor(capacity=capacity, retry_strategy=retry_strategy),
            cache_strategy=cache_strategy,
        )
        self.model = model
        self.kwargs = dict(cohere_kwargs)

        async def chat(messages: Any, documents=None, **kwargs) -> tuple:
            import cohere  # gated

            client = cohere.AsyncClient()
            ret = await client.chat(
                message=_messages_to_prompt(messages),
                model=self.model,
                documents=documents,
                **{**self.kwargs, **kwargs},
            )
            cited = [dict(c.__dict__) for c in (ret.citations or [])]
            return (ret.text, tuple(map(str, cited)))

        self.__wrapped__ = chat


class HFPipelineChat(BaseChat):
    """Local transformers pipeline chat (parity: llms.py HFPipelineChat).

    Works offline when the model is in the local HF cache; the reference
    runs this on CPU/GPU torch — ``JaxChat`` below is the TPU-native
    serving path for the generation side.
    """

    def __init__(
        self,
        model: str | None = "gpt2",
        call_kwargs: dict = {},
        device: str = "cpu",
        **pipeline_kwargs,
    ):
        super().__init__()
        self.model = model
        self.call_kwargs = dict(call_kwargs)
        self.pipeline_kwargs = dict(pipeline_kwargs)
        self._pipeline = None

        def chat(messages: Any, **kwargs) -> str | None:
            pipe = self._get_pipeline()
            prompt = _messages_to_prompt(messages)
            out = pipe(prompt, **{**self.call_kwargs, **kwargs})
            text = out[0]["generated_text"]
            if isinstance(text, str) and text.startswith(prompt):
                text = text[len(prompt):]
            return text

        self.__wrapped__ = chat

    def _get_pipeline(self):
        if self._pipeline is None:
            import os

            os.environ.setdefault("HF_HUB_OFFLINE", "1")
            os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
            from transformers import pipeline  # gated offline

            self._pipeline = pipeline(
                "text-generation", model=self.model, **self.pipeline_kwargs
            )
        return self._pipeline

    def crop_to_max_prompt_size(self, text: str, max_tokens: int = 1024) -> str:
        return text[: max_tokens * 4]


class JaxChat(BaseChat):
    """TPU-native local chat: a jitted JAX decoder behind the continuous-
    batching scheduler.

    The reference's local-serving story is a host-side torch pipeline
    (``xpacks/llm/llms.py:314`` HFPipelineChat; the Adaptive RAG template
    runs Mistral-7B-Instruct through it).  Here every row, whatever its
    sampling options, is submitted to the process-wide
    :func:`pathway_tpu.serving.generation.shared_scheduler` of its model:
    rows of all routes and epochs share one running batch over a paged KV
    cache, prefilled in chunks and decoded a token a step
    (``models/decoder.py``), device-resident end to end.  A locally cached
    llama/mistral-family checkpoint is mapped in when present; otherwise
    deterministic random weights keep shapes/FLOPs (and thus serving
    latency) identical.
    """

    def __init__(
        self,
        model: str = "mistral-7b-instruct",
        max_new_tokens: int = 128,
        temperature: float = 0.0,
        max_cache: int = 1024,
        capacity: int | None = None,
        cache_strategy=None,
        quantize: str | None = None,
    ):
        super().__init__(
            executor=async_executor(capacity=capacity),
            cache_strategy=cache_strategy,
        )
        self.model = model
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.max_cache = max_cache
        if quantize not in (None, "int8"):  # fail at config time, not first row
            raise ValueError(f"quantize must be None or 'int8', got {quantize!r}")
        self.quantize = quantize
        self._built = False
        self._init_lock = None

        async def chat(messages: Any, **kwargs) -> str:
            import asyncio

            if not self._built:
                # the first call builds the model and its pools; keep the
                # loop free while it does, and hold a lock so concurrent
                # rows wait for one build
                if self._init_lock is None:
                    self._init_lock = asyncio.Lock()
                async with self._init_lock:
                    if not self._built:
                        await asyncio.to_thread(self._scheduler)
                        self._built = True

            def option(name, kind):
                # coerce here: a malformed kwarg fails with a clear
                # TypeError, not inside the scheduler's thread
                value = kwargs.get(name)
                return None if value is None else kind(value)

            fut = self._scheduler().submit(
                _messages_to_prompt(messages),
                max_new_tokens=int(kwargs.get("max_tokens", self.max_new_tokens)),
                temperature=float(kwargs.get("temperature", self.temperature)),
                top_p=option("top_p", float),
                min_p=option("min_p", float),
                top_k=option("top_k", int),
                repetition_penalty=option("repetition_penalty", float),
            )
            return await asyncio.wrap_future(fut)

        self.__wrapped__ = chat

    def _scheduler(self):
        from pathway_tpu.serving import generation

        return generation.shared_scheduler(
            self.model, max_cache=self.max_cache, quantize=self.quantize
        )

    def crop_to_max_prompt_size(self, text: str, max_tokens: int = 1024) -> str:
        return text[: max_tokens * 4]


def prompt_chat_single_qa(question: ColumnExpression) -> ColumnExpression:
    """Wrap a question column into a single-message chat (llms.py helper)."""
    from pathway_tpu.internals import dtype as dt

    return expr_mod.ApplyExpression(
        lambda q: Json([{"role": "user", "content": q}]),
        dt.JSON,
        question,
    )
